"""Mixture-of-Experts with expert parallelism over an 'ep' mesh axis.

New capability vs the reference (SURVEY §2.2 confirms: "no expert
parallelism" anywhere in the tree — its MoE era came later with
incubate.distributed.models.moe built on manual alltoall ops). Designed
TPU-first per the GShard/Switch pattern:

  - experts' FFN params are stacked [E, ...] and sharded over mesh axis
    'ep' (PartitionSpec("ep", ...)); token dispatch/combine are a sorted
    scatter/gather pair — tokens are argsorted by routed expert, assigned
    capacity slots by position within their expert's segment, scattered
    into the [E, capacity, H] expert buffer and gathered back weighted by
    their gate. O(T·K·log + E·C·H) work and memory; no [T, E, C] one-hot
    ever materializes (the dense-dispatch design is ruinous at real
    expert counts). GSPMD lowers the expert-sharded scatter/gather to the
    data exchange the reference era would have hand-written with NCCL
    alltoall,
  - top-1 (Switch) or top-2 (GShard) routing with a capacity factor;
    overflow tokens fall through the residual (standard Switch behavior),
  - the Switch load-balance auxiliary loss (E * Σ_e fraction_e · prob_e)
    is exposed as ``layer.aux_loss`` for the model to add.

``dropless_moe`` beside it is token choice as today's open sparse models
publish it (OLMoE, arXiv:2409.02060): every token keeps all its ``top_k``
experts whatever the imbalance. There is no capacity: the ``T x k``
assignments are ordered by expert and the experts run as grouped matrix
multiplications over ragged groups (``ops.grouped_matmul``: the Pallas
kernels ``moe_gmm``/``moe_tgmm`` on one TPU device, ``jax.lax.ragged_dot``
on the CPU, under a multi-device mesh, which GSPMD partitions it over, and
for shapes that do not tile; either way exactly the needed operations,
forward and both backward products).

Composes with dp/tp/ep through the strategy compiler
(compile_train_step picks up the P("ep", ...) param_shardings and the
model.loss aux term) AND with pipeline parallelism: blocks return
``(h, aux)`` and ``pipeline_apply(stage_aux=True)`` carries the
load-balance scalar across the schedule (fill/drain ticks masked,
psum over 'pp', per-microbatch mean) — see distributed/hybrid.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import nn
from ..framework.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import grouped_matmul as _gmm
from ..profiler.trace import annotate as _annotate
from ..tensor._helper import apply

__all__ = ["MoEMLP", "switch_moe", "DroplessMoEMLP", "dropless_moe",
           "publish_expert_load"]


# ---------------------------------------------------------------------------
# injective-gather dispatch/combine with gather-only VJPs
#
# Autodiff turns the dispatch gather's backward into a scatter-add — but
# within a routing round each token occupies at most ONE capacity slot
# (the map is injective), so the transpose is itself a gather through the
# inverse map. TPU gathers vectorize; row scatter-adds serialize. Both
# primitives below carry the inverse maps and declare the gather-form
# VJPs explicitly.
# ---------------------------------------------------------------------------
@jax.custom_vjp
def _dispatch_gather(x, token_of_slot, slot_of_token, valid):
    """xe_flat[s] = x[token_of_slot[s]].

    slot_of_token [K, T] (clamped), valid [K, T]: per routing round, the
    slot each token landed in. VJP: dx[t] = sum_k valid[k,t] ? g[slot_of_
    token[k,t]] : 0 — pure gathers."""
    return x[token_of_slot]


def _dispatch_fwd(x, token_of_slot, slot_of_token, valid):
    return x[token_of_slot], (slot_of_token, valid)


def _dispatch_bwd(res, g):
    slot_of_token, valid = res
    dx = None
    for k in range(slot_of_token.shape[0]):
        dk = jnp.where(valid[k][:, None], g[slot_of_token[k]], 0)
        dx = dk if dx is None else dx + dk
    return (dx, None, None, None)


_dispatch_gather.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine_gather(ye, gates, slot_of_token, valid, token_of_slot,
                    round_of_slot, occupied):
    """y[t] = sum_k valid[k,t] * gates[k,t] * ye[slot_of_token[k,t]].

    VJP w.r.t. ye: dye[s] = occupied[s] ? dy[token_of_slot[s]] *
    gates[round_of_slot[s], token_of_slot[s]] : 0 — a gather (each slot
    holds one token), not the scatter-add autodiff would emit.
    """
    y = None
    for k in range(slot_of_token.shape[0]):
        w = (gates[k] * valid[k]).astype(ye.dtype)[:, None]
        c = ye[slot_of_token[k]] * w
        y = c if y is None else y + c
    return y


def _combine_fwd(ye, gates, slot_of_token, valid, token_of_slot,
                 round_of_slot, occupied):
    out = _combine_gather(ye, gates, slot_of_token, valid, token_of_slot,
                          round_of_slot, occupied)
    return out, (ye, gates, slot_of_token, valid, token_of_slot,
                 round_of_slot, occupied)


def _combine_bwd(res, dy):
    ye, gates, slot_of_token, valid, token_of_slot, round_of_slot, \
        occupied = res
    # dye: gather dy through each slot's occupying token
    wsel = gates[round_of_slot, token_of_slot].astype(ye.dtype)
    dye = jnp.where(occupied[:, None],
                    dy[token_of_slot] * wsel[:, None], 0)
    # dgates[k, t] = valid ? <dy[t], ye[slot_k_t]> : 0
    dgs = []
    for k in range(slot_of_token.shape[0]):
        contrib = jnp.sum(dy.astype(jnp.float32)
                          * ye[slot_of_token[k]].astype(jnp.float32),
                          axis=-1)
        dgs.append(jnp.where(valid[k], contrib, 0.0))
    dgates = jnp.stack(dgs)
    return (dye, dgates, None, None, None, None, None)


_combine_gather.defvjp(_combine_fwd, _combine_bwd)


def switch_moe(x, gate_w, w_in, b_in, w_out, b_out, *, top_k=1,
               capacity_factor=1.25):
    """Pure-jax MoE FFN. x: [T, H]; gate_w: [H, E]; experts stacked
    w_in [E, H, F], b_in [E, F], w_out [E, F, H], b_out [E, H].

    Returns (y [T, H], aux_loss scalar).
    """
    t, h = x.shape
    e = gate_w.shape[1]
    cap = max(1, int(np.ceil(capacity_factor * top_k * t / e)))

    # All routing math runs in the TRANSPOSED [E, T] layout: with E of 8
    # and T of thousands, [T, E] puts the long axis on sublanes and an
    # 8-wide minor dim on the 128-lane VPU — every softmax/argmax/cumsum
    # wastes 94% of the lanes (round-5 profile: the routing pipeline cost
    # more than the expert FFN fwd+bwd). [E, T] keeps T on the lanes.
    # moe/* named scopes: routing/dispatch/experts/combine phase names
    # traced into the program for device-time attribution (profiler)
    with _annotate("moe/route"):
        logits_t = jnp.dot(gate_w.astype(x.dtype).T, x.T)  # [E, T]
        probs_t = jax.nn.softmax(logits_t.astype(jnp.float32), axis=0)

        # -- routing: top_k rounds over [E, T] (never [T, E, C]) ----------
        expert_rounds, gate_rounds = [], []
        remaining = probs_t
        aux_fraction = jnp.zeros((e,), jnp.float32)
        for _ in range(top_k):
            idx = jnp.argmax(remaining, axis=0)            # [T]
            onehot_t = (jnp.arange(e, dtype=jnp.int32)[:, None]
                        == idx[None, :]).astype(jnp.float32)   # [E, T]
            expert_rounds.append(idx.astype(jnp.int32))
            gate_rounds.append(jnp.sum(remaining * onehot_t, axis=0))
            aux_fraction = aux_fraction + jnp.mean(onehot_t, axis=1)
            remaining = remaining * (1.0 - onehot_t)

    # -- dispatch: cumsum slot assignment, gather-only data movement ------
    # Round-4 profile: the argsort([K*T]) bitonic network + two full-row
    # H-wide scatters dominated the step (MoE MFU 0.29). Slot-within-
    # expert is just "how many earlier entries routed here", which a
    # [T, E] cumsum answers directly (GShard position_in_expert); earlier
    # routing rounds take earlier capacity slots via a running per-expert
    # offset. The only scatter left is int32 token ids into [E*cap]; the
    # wide data movement is a gather in (x[token_of_slot]) and a gather
    # out per round — TPU gathers vectorize, row scatters serialize.
    prior = jnp.zeros((e,), jnp.float32)                   # slots used
    slot_rounds, keep_rounds = [], []
    for k in range(top_k):
        onehot_t = (jnp.arange(e, dtype=jnp.int32)[:, None]
                    == expert_rounds[k][None, :]).astype(jnp.float32)
        pos_in_round = (jnp.cumsum(onehot_t, axis=1)
                        - onehot_t)                        # [E, T]
        pos = (jnp.sum(pos_in_round * onehot_t, axis=0)
               + prior[expert_rounds[k]]).astype(jnp.int32)  # [T]
        prior = prior + jnp.sum(onehot_t, axis=1)
        keep = pos < cap
        # overflow entries target row E*cap, dropped by scatter mode="drop"
        slot_rounds.append(jnp.where(keep, expert_rounds[k] * cap + pos,
                                     e * cap))
        keep_rounds.append(keep)

    slot_flat = jnp.concatenate(slot_rounds)               # [K*T]
    token_flat = jnp.tile(jnp.arange(t, dtype=jnp.int32), top_k)
    round_flat = jnp.repeat(jnp.arange(top_k, dtype=jnp.int32), t)
    token_of_slot = jnp.zeros((e * cap + 1,), jnp.int32).at[slot_flat] \
        .set(token_flat, mode="drop")[:e * cap]
    round_of_slot = jnp.zeros((e * cap + 1,), jnp.int32).at[slot_flat] \
        .set(round_flat, mode="drop")[:e * cap]
    occupied = jnp.zeros((e * cap + 1,), bool).at[slot_flat] \
        .set(True, mode="drop")[:e * cap]
    slot_of_token = jnp.stack(
        [jnp.minimum(s, e * cap - 1) for s in slot_rounds])  # [K, T]
    valid = jnp.stack(keep_rounds)                           # [K, T]

    with _annotate("moe/dispatch"):
        xe = _dispatch_gather(x, token_of_slot, slot_of_token,
                              valid).reshape(e, cap, h)
    # empty slots compute x[0]'s row; no token combines them and the
    # combine VJP masks them, so no spurious weight gradient flows
    with _annotate("moe/experts"):
        hmid = jax.nn.gelu(
            jnp.einsum("ech,ehf->ecf", xe, w_in.astype(x.dtype))
            + b_in.astype(x.dtype)[:, None, :])
        ye = (jnp.einsum("ecf,efh->ech", hmid, w_out.astype(x.dtype))
              + b_out.astype(x.dtype)[:, None, :]).reshape(e * cap, h)

    # -- combine: per-round gather of each token's slot, gate-weighted ----
    with _annotate("moe/combine"):
        gates = jnp.stack(gate_rounds)                       # [K, T] f32
        y = _combine_gather(ye, gates, slot_of_token, valid, token_of_slot,
                            round_of_slot, occupied)

    # Switch aux loss: E * sum_e fraction_e * mean-prob_e
    aux = e * jnp.sum((aux_fraction / top_k)
                      * jnp.mean(probs_t, axis=1))
    return y, aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# drop-less token choice
# ---------------------------------------------------------------------------
def publish_expert_load(stats) -> None:
    """The drop-less layers' counters in the profiler's registry, on the
    host, from the ``stats`` a forward or a training step left
    (``DroplessMoEMLP.stats``, ``HybridPipelineTrainer.aux_stats``: sums
    over the expert-layer calls of the step). ``moe/dropped_tokens`` keeps
    the most any step lost (0 by construction: every assignment has a row
    in some expert's group) and ``moe/expert_load_max_over_mean`` the last
    step's fullest expert over the mean one, averaged over its calls."""
    from ..profiler import metrics

    rows = np.asarray(stats["moe/rows"])
    assigned = float(stats["moe/assigned"])
    reg = metrics.registry()
    reg.gauge("moe/dropped_tokens").set_max(
        int(round(assigned - float(rows.sum()))))
    reg.gauge("moe/expert_load_max_over_mean").set(
        float(stats["moe/load_max"]) * rows.size / max(assigned, 1.0))


def _expert_product(rows, w, group_sizes):
    """One of the three grouped products, counted at trace time under the
    path it takes (``moe/grouped_matmul_calls{path=pallas|xla}`` in the
    profiler's registry: a count on the host, nothing in the program) and,
    where that is the kernel, under the tile it walks a group's matrix in
    (``moe/grouped_matmul_tiles{tile=128x2560x768}``: rows, contraction
    and columns a grid step, ``ops.grouped_matmul.tile_for``)."""
    from ..profiler import metrics

    shape = rows.shape[0], rows.shape[1], w.shape[2]
    path = _gmm.kernel_path(*shape)
    reg = metrics.registry()
    reg.counter("moe/grouped_matmul_calls{path=%s}" % path).add(1)
    if path == "pallas":
        reg.counter("moe/grouped_matmul_tiles{tile=%dx%dx%d}"
                    % _gmm.tile_for(*shape)).add(1)
    return _gmm.grouped_matmul(rows, w.astype(rows.dtype), group_sizes)


def _swiglu_rows(xs, gate_of_row, w_gate, w_up, w_down, sizes, live):
    """The held experts on one window of sorted rows, each row times its
    routing weight; rows past the groups' end are zeros, whatever the
    kernels left there."""
    mid = jax.nn.silu(_expert_product(xs, w_gate, sizes)) * \
        _expert_product(xs, w_up, sizes)
    ys = _expert_product(mid, w_down, sizes)
    return jnp.where(live, ys * gate_of_row.astype(ys.dtype)[:, None], 0)


def _held_window(token_of_row, gate_of_row, group_sizes, p, width):
    """Window ``p`` of the sorted rows: the tokens and weights of its
    ``width`` rows, the part of every group that lies in it, and which of
    its rows exist."""
    lo = p * width
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    sizes = jnp.clip(ends - lo, 0, width) - jnp.clip(starts - lo, 0, width)
    live = (lo + jnp.arange(width, dtype=jnp.int32) < ends[-1])[:, None]
    return (jax.lax.dynamic_slice(token_of_row, (lo,), (width,)),
            jax.lax.dynamic_slice(gate_of_row, (lo,), (width,)),
            sizes.astype(jnp.int32), live)


#: ``t * h`` up to which ``_combine`` takes the 0/1 product. A row of the
#: product costs ``2 * t * h`` operations, a row of XLA's scatter-add with
#: repeated indices (it sorts them and adds row by row) does not grow so. On
#: the v5e (PERF.md section 6, PR 52): the serving ticks' windows, 0.8-2.7 M,
#: 12-23 us where the scatter-add took 72-1,050; Solar's step, ``(8192, 2560,
#: 4096)`` = 33.6 M, 0.99 ms a window where the scatter-add, in place there,
#: takes 0.66 (``train_tokens_per_s_per_chip`` -0.22 % with the product, in
#: both of two pairs). No cell lies between; the line stands at 2**24.
_ONEHOT_MAX_T_X_H = 1 << 24


def _combine_onehot(y, tok, rows):
    """``y.at[tok].add(rows)`` as a product on the MXU: ``onehot[t, r] =
    (tok[r] == t)`` in the rows' dtype times ``rows``. A 0/1 operand times a
    row is exact and the rows of a token are summed in float32 and cast
    once, where the scatter-add rounds to ``y``'s dtype after every row.
    **A value that is not finite spoils its column of every token**, not of
    its own alone: ``0 * inf`` is NaN in the product."""
    onehot = (jnp.arange(y.shape[0], dtype=jnp.int32)[:, None]
              == tok[None, :]).astype(rows.dtype)
    return (y + jnp.dot(onehot, rows, precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)).astype(y.dtype)


def _combine(y, tok, rows):
    """A window's rows added to their tokens, counted at trace time under
    the form taken (``moe/combine_calls{path=onehot|scatter}`` in the
    profiler's registry, as ``_expert_product`` counts its path)."""
    from ..profiler import metrics

    t, h = y.shape
    path = "onehot" if t * h <= _ONEHOT_MAX_T_X_H else "scatter"
    metrics.registry().counter("moe/combine_calls{path=%s}" % path).add(1)
    if path == "scatter":
        return y.at[tok].add(rows)
    return _combine_onehot(y, tok, rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _held_experts(x, gate_of_row, w_gate, w_up, w_down, token_of_row,
                  group_sizes, width):
    """``y[t] = sum over the rows r of token t of gate_of_row[r] *
    expert(r)(x[t])`` for the assignments this chip holds, sorted by
    expert: ``token_of_row`` and ``gate_of_row`` [n_max] (``n_max`` a
    multiple of ``width``), ``group_sizes`` [held] the rows of each.

    Exact for any routing at the cost of the rows there are: the rows go
    through in windows of ``width`` (gather, three grouped products, the
    rows added to their tokens: ``_combine``), the first always, the others
    while rows are left, so the buffers are ``[width, H]`` and a routing
    that sends everything here takes ``n_max / width`` rounds, not more
    memory. The backward pass walks the same windows and recomputes each."""
    def add(p, y):
        tok, gt, sizes, live = _held_window(token_of_row, gate_of_row,
                                            group_sizes, p, width)
        with _annotate("moe/dispatch"):
            xs = jnp.where(live, x[tok], 0)
        with _annotate("moe/experts"):
            rows = _swiglu_rows(xs, gt, w_gate, w_up, w_down, sizes, live)
        with _annotate("moe/combine"):
            return _combine(y, tok, rows)

    windows = (jnp.sum(group_sizes) + width - 1) // width
    return jax.lax.fori_loop(1, windows, add, add(0, jnp.zeros_like(x)))


def _held_fwd(x, gate_of_row, w_gate, w_up, w_down, token_of_row,
              group_sizes, width):
    y = _held_experts(x, gate_of_row, w_gate, w_up, w_down, token_of_row,
                      group_sizes, width)
    return y, (x, gate_of_row, w_gate, w_up, w_down, token_of_row,
               group_sizes)


def _held_bwd(width, res, dy):
    x, gate_of_row, w_gate, w_up, w_down, token_of_row, group_sizes = res

    def add(p, acc):
        dx, dgate, dwg, dwu, dwd = acc
        tok, gt, sizes, live = _held_window(token_of_row, gate_of_row,
                                            group_sizes, p, width)
        with _annotate("moe/dispatch"):
            xs = jnp.where(live, x[tok], 0)
        with _annotate("moe/combine"):
            drows = dy[tok]
        with _annotate("moe/experts"):
            _, vjp = jax.vjp(
                lambda xs_, gt_, wg_, wu_, wd_: _swiglu_rows(
                    xs_, gt_, wg_, wu_, wd_, sizes, live),
                xs, gt, w_gate, w_up, w_down)
            dxs, dgt, g1, g2, g3 = vjp(drows)
        with _annotate("moe/dispatch"):
            dx = dx.at[tok].add(jnp.where(live, dxs, 0))
        return (dx, jax.lax.dynamic_update_slice(dgate, dgt, (p * width,)),
                dwg + g1, dwu + g2, dwd + g3)

    windows = (jnp.sum(group_sizes) + width - 1) // width
    zeros = (jnp.zeros_like(x), jnp.zeros_like(gate_of_row),
             jnp.zeros_like(w_gate), jnp.zeros_like(w_up),
             jnp.zeros_like(w_down))
    dx, dgate, dwg, dwu, dwd = jax.lax.fori_loop(1, windows, add,
                                                 add(0, zeros))
    return dx, dgate, dwg, dwu, dwd, None, None


_held_experts.defvjp(_held_fwd, _held_bwd)


def held_window_rows(t: int, top_k: int, held: int, e: int) -> int:
    """Rows a window of ``_held_experts`` takes: half above the ``t *
    top_k * held / e`` a balanced router sends to ``held`` of ``e``
    experts, in the grouped kernels' 128-row tiles, never more than there
    can be."""
    most = -(-t * min(top_k, held) // 128) * 128
    fair = -(-3 * t * top_k * held // (2 * e))
    return min(most, -(-fair // 128) * 128)


def kept_groups(scores_t, n_group: int, topk_group: int, best: int = 1):
    """The group limit of DeepSeek's routers: the experts ``[E, T]`` are
    ``n_group`` runs of ``E / n_group``, a group's score is the sum of its
    ``best`` largest experts' (1: DeepSeek-V2's ``group_limited_greedy``,
    arXiv:2405.04434 section 2.2's device-limited routing, over softmax
    scores; 2: DeepSeek-V3's ``noaux_tc``, over sigmoid scores plus the
    selection bias), and a token keeps its ``topk_group`` best groups
    (rounds of argmax, a tie to the lower group, as the experts' rounds).
    Returns bool ``[n_group, T]``."""
    e, t = scores_t.shape
    by_group = scores_t.reshape(n_group, e // n_group, t)
    remaining = jnp.max(by_group, axis=1)
    for _ in range(best - 1):
        # the next largest of each group: the first of the largest set aside
        rows_m = jnp.arange(e // n_group, dtype=jnp.int32)[None, :, None]
        first = jnp.argmax(by_group, axis=1).astype(jnp.int32)[:, None, :]
        by_group = jnp.where(rows_m == first, -jnp.inf, by_group)
        remaining = remaining + jnp.max(by_group, axis=1)
    rows_g = jnp.arange(n_group, dtype=jnp.int32)[:, None]
    kept = jnp.zeros((n_group, t), bool)
    for _ in range(topk_group):
        hit = rows_g == jnp.argmax(remaining, axis=0).astype(jnp.int32)[None]
        kept = kept | hit
        remaining = jnp.where(hit, -jnp.inf, remaining)
    return kept


def _route(x, router_w, top_k, scoring="softmax", select_bias=None,
           held=None, n_group=1, topk_group=1):
    """The routing both drop-less layers share, in the transposed [E, T]
    layout, T on the lanes, and ``top_k`` as rounds of argmax, as
    ``switch_moe`` does and for its reasons: the scores, the experts and
    gates of each round and, **a counting sort**, every assignment's place
    within its expert's group (rows of earlier rounds, then the earlier
    tokens of this round), kept for the experts ``held=(first, count)``
    alone where that is given. ``n_group`` > 1: a token chooses within its
    ``topk_group`` best of ``n_group`` groups of experts (``kept_groups``).
    Over softmax scores (DeepSeek-V2's) a group's score is its best
    expert's and the others' scores are set to 0 before the rounds; over
    sigmoid scores (DeepSeek-V3's ``noaux_tc``) it is the sum of its two
    largest ``score + select_bias`` and the others leave the rounds.
    Returns ``(probs_t [E, T], z, expert_rounds, gate_rounds, pos_rounds,
    counts)``, ``counts`` [E] or [count] float32 the rows given out."""
    e = router_w.shape[1]
    softmax = scoring == "softmax"
    held_rows = (lambda a: a) if held is None \
        else (lambda a: a[held[0]:held[0] + held[1]])
    logits_t = jnp.dot(router_w.astype(x.dtype).T, x.T,
                       preferred_element_type=jnp.float32)       # [E, T]
    if softmax:
        lse = jax.nn.logsumexp(logits_t, axis=0)                 # [T]
        probs_t = jnp.exp(logits_t - lse[None, :])
        z = jnp.mean(jnp.square(lse))
        remaining = probs_t
        if n_group > 1:
            remaining = jnp.where(jnp.repeat(
                kept_groups(probs_t, n_group, topk_group), e // n_group,
                axis=0), probs_t, 0.0)
    else:
        probs_t = jax.nn.sigmoid(logits_t)
        z = jnp.zeros((), jnp.float32)
        remaining = probs_t if select_bias is None else probs_t + \
            jax.lax.stop_gradient(select_bias).astype(jnp.float32)[:, None]
        if n_group > 1:
            remaining = jnp.where(jnp.repeat(
                kept_groups(remaining, n_group, topk_group, best=2),
                e // n_group, axis=0), remaining, -jnp.inf)
    rows_e = jnp.arange(e, dtype=jnp.int32)[:, None]
    counts = jnp.zeros((e if held is None else held[1],), jnp.float32)
    expert_rounds, gate_rounds, pos_rounds = [], [], []
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=0).astype(jnp.int32)    # [T]
        onehot_t = (rows_e == idx[None, :]).astype(jnp.float32)
        gate_rounds.append(jnp.sum(
            (remaining if softmax else probs_t) * onehot_t, axis=0))
        mine = held_rows(onehot_t)
        before = jnp.cumsum(mine, axis=1) - mine
        pos_rounds.append(jnp.sum((before + counts[:, None]) * mine,
                                  axis=0).astype(jnp.int32))
        counts = counts + jnp.sum(mine, axis=1)
        expert_rounds.append(idx)
        remaining = remaining * (1.0 - onehot_t) if softmax \
            else jnp.where(onehot_t > 0, -jnp.inf, remaining)
    return probs_t, z, expert_rounds, gate_rounds, pos_rounds, counts


def dropless_moe(x, router_w, w_gate, w_up, w_down, top_k):
    """Token-choice MoE FFN with no token dropped. x: [T, H]; router_w:
    [H, E], no bias; SiLU-gated experts stacked w_gate, w_up [E, H, F] and
    w_down [E, F, H], no biases.

    Softmax over all E in float32, ``top_k`` experts a token by rounds of
    argmax, the chosen probabilities as weights **unrenormalised**. Then a
    counting sort of the ``T * top_k`` assignments by expert (``_route``),
    rows gathered to ``[T*top_k, H]``, three grouped matmuls over the [E]
    group sizes, gate-weighted un-sort and sum over ``top_k``.
    ``held_moe`` is the layer that holds a share of its experts.

    Returns ``(y [T, H], balance, z, rows)``: ``rows`` [E] int32 are the
    assignments each expert was given, what the grouped matmuls are told
    to compute (they sum to ``T * top_k``: none is dropped); ``balance`` is HF
    ``load_balancing_loss_func`` on this layer's logits, ``E * sum_{j,e}
    f[j,e] * P[e]`` with ``f[j,e]`` the share of tokens whose j-th choice
    is ``e`` and ``P[e]`` the mean probability of ``e`` (``top_k`` when
    balanced); ``z`` is the router z-loss, the mean over tokens of
    ``logsumexp(logits)**2``. The caller weighs them.
    """
    t, h = x.shape
    e = router_w.shape[1]
    n = t * top_k
    with _annotate("moe/route"):
        probs_t, z, expert_rounds, gate_rounds, pos_rounds, counts = \
            _route(x, router_w, top_k)
        balance = e * jnp.sum((counts / t) * jnp.mean(probs_t, axis=1))
        group_sizes = counts.astype(jnp.int32)                   # [E]
        starts = jnp.cumsum(group_sizes) - group_sizes
        row_of_token = jnp.stack(
            [starts[expert_rounds[k]] + pos_rounds[k]
             for k in range(top_k)])                             # [K, T]
        # the inverse map: a permutation, so the int32 scatter is exact
        flat = row_of_token.reshape(n)
        token_of_row = jnp.zeros((n,), jnp.int32).at[flat].set(
            jnp.tile(jnp.arange(t, dtype=jnp.int32), top_k),
            unique_indices=True)
        round_of_row = jnp.zeros((n,), jnp.int32).at[flat].set(
            jnp.repeat(jnp.arange(top_k, dtype=jnp.int32), t),
            unique_indices=True)
        gates = jnp.stack(gate_rounds)                           # [K, T]
    every = jnp.ones((top_k, t), bool)
    with _annotate("moe/dispatch"):
        xs = _dispatch_gather(x, token_of_row, row_of_token, every)
    with _annotate("moe/experts"):
        mid = jax.nn.silu(_expert_product(xs, w_gate, group_sizes)) * \
            _expert_product(xs, w_up, group_sizes)
        ys = _expert_product(mid, w_down, group_sizes)
    with _annotate("moe/combine"):
        y = _combine_gather(ys, gates, row_of_token, every, token_of_row,
                            round_of_row, jnp.ones((n,), bool))
    return (y, balance.astype(jnp.float32), z.astype(jnp.float32),
            group_sizes)


def held_moe(x, router_w, w_gate, w_up, w_down, top_k, held,
             scoring="sigmoid", select_bias=None, shared=None,
             n_group=1, topk_group=1, routed_scaling=1.0):
    """``dropless_moe``'s sibling that **holds a share of its experts**, as
    one rank of an expert-parallel layout does: experts ``first : first +
    count`` of the router's E, ``held=(first, count)``, weights ``[count,
    H, F]``. It shares the routing (``_route``, over all E) and nothing
    after it, because what follows must cost what the rows held cost and
    not ``T * top_k``: the held assignments' rows, sorted by expert, go
    through ``_held_experts`` in windows (gather, the three grouped
    products, ``_combine``), where the full layer's gather-only dispatch and
    combine read every assignment's row. Every assignment to a held expert
    is computed, whatever share of the ``T * top_k`` falls here; what the
    absent experts would add is left out.

    ``scoring="sigmoid"``: the scores are ``sigmoid(logits)``, the
    ``top_k`` largest of ``score + select_bias`` [E] are chosen (the bias
    selects only and takes no gradient) and the chosen scores weigh their
    experts **renormalised** over all ``top_k`` chosen, held or not;
    ``"softmax"``: as ``dropless_moe``, unrenormalised.
    ``shared=(w_gate, w_up, w_down)`` [H, F], [H, F], [F, H] adds one
    SwiGLU expert every token passes (two shared experts of 1,536 are one
    of 3,072). ``n_group``, ``topk_group``: the router's group limit, in
    its scoring's form (``_route``); ``routed_scaling`` multiplies the routed experts'
    weights and not the shared expert. At their defaults the program is
    what it was without them.

    Returns ``(y [T, H], rows)``, ``rows`` [count] int32 the assignments
    each held expert was given and computed. No auxiliary term: the
    lineage that routes so balances by the selection bias."""
    t, h = x.shape
    e = router_w.shape[1]
    n = t * top_k
    first, count = held
    with _annotate("moe/route"):
        _, _, expert_rounds, gate_rounds, pos_rounds, counts = _route(
            x, router_w, top_k, scoring, select_bias, held, n_group,
            topk_group)
        group_sizes = counts.astype(jnp.int32)                   # [count]
        starts = jnp.cumsum(group_sizes) - group_sizes
        gates = jnp.stack(gate_rounds)                           # [K, T]
        if scoring != "softmax":
            gates = gates / jnp.sum(gates, axis=0, keepdims=True)
        if routed_scaling != 1.0:
            gates = gates * routed_scaling
        # the held assignments' rows, sorted by expert; the others land
        # past the end, one place each, and are cut off
        width = held_window_rows(t, top_k, count, e)
        n_max = -(-t * min(top_k, count) // width) * width
        local = jnp.stack(expert_rounds) - first                 # [K, T]
        here = (local >= 0) & (local < count)
        flat = jnp.where(
            here, starts[jnp.clip(local, 0, count - 1)]
            + jnp.stack(pos_rounds),
            n_max + jnp.arange(n, dtype=jnp.int32).reshape(top_k, t)
        ).reshape(n)
        token_of_row = jnp.zeros((n_max + n,), jnp.int32).at[flat].set(
            jnp.tile(jnp.arange(t, dtype=jnp.int32), top_k),
            unique_indices=True)[:n_max]
        gate_of_row = jnp.zeros((n_max + n,), jnp.float32).at[flat].set(
            gates.reshape(n), unique_indices=True)[:n_max]
    y = _held_experts(x, gate_of_row, w_gate, w_up, w_down, token_of_row,
                      group_sizes, width)
    if shared is not None:
        with _annotate("moe/shared"):
            s_gate, s_up, s_down = (w.astype(x.dtype) for w in shared)
            y = y + jnp.dot(jax.nn.silu(jnp.dot(x, s_gate))
                            * jnp.dot(x, s_up), s_down)
    return y, group_sizes


class DroplessMoEMLP(nn.Layer):
    """The drop-less MoE FFN of a transformer block (``dropless_moe``).

    forward(x [B, S, H]) -> [B, S, H]; the two auxiliary terms of the last
    forward are ``balance_loss`` and ``z_loss`` (unweighted scalars; the
    block weighs them, models/gpt.py GPTBlock). ``stats`` are its counts,
    float32 so that they can be summed along the trainer's auxiliary carry
    and leave the step as outputs: ``moe/rows`` [E] the assignments each
    expert was given, ``moe/load_max`` the fullest expert's,
    ``moe/assigned`` all there were (tokens x ``top_k``).
    ``publish_expert_load`` turns them into the registry's gauges on the
    host, after the step: nothing in the program talks to the host."""

    def __init__(self, hidden_size: int, expert_width: int,
                 num_experts: int, top_k: int,
                 initializer_range: float = 0.02,
                 out_initializer_range: float = 0.02):
        super().__init__()
        init = I.Normal(0.0, initializer_range)
        e, h, f = num_experts, hidden_size, expert_width
        self.num_experts = e
        self.top_k = top_k
        self.gate = self.create_parameter([h, e], default_initializer=init)
        self.w_gate = self.create_parameter([e, h, f],
                                            default_initializer=init)
        self.w_up = self.create_parameter([e, h, f],
                                          default_initializer=init)
        self.w_down = self.create_parameter(
            [e, f, h],
            default_initializer=I.Normal(0.0, out_initializer_range))
        # expert dim sharded over 'ep', as MoEMLP declares it
        self.param_shardings = {
            "gate": P(), "w_gate": P("ep", None, None),
            "w_up": P("ep", None, None), "w_down": P("ep", None, None)}
        self.balance_loss = Tensor(jnp.zeros((), jnp.float32))
        self.z_loss = Tensor(jnp.zeros((), jnp.float32))
        self.stats = {}

    def forward(self, x):
        b, s, h = x.shape[0], x.shape[1], x.shape[2]

        def f(xv, gw, wg, wu, wd):
            y, balance, z, rows = dropless_moe(xv.reshape(b * s, h), gw, wg,
                                               wu, wd, self.top_k)
            rows = rows.astype(jnp.float32)
            return (y.reshape(b, s, h), balance, z, rows, jnp.max(rows),
                    jnp.float32(b * s * self.top_k))

        y, self.balance_loss, self.z_loss, rows, load_max, assigned = apply(
            f, x, self.gate, self.w_gate, self.w_up, self.w_down,
            name="dropless_moe")
        self.stats = {"moe/rows": rows, "moe/load_max": load_max,
                      "moe/assigned": assigned}
        return y


class HeldMoEMLP(nn.Layer):
    """One rank's share of an expert-parallel drop-less layer
    (``held_moe``): the router keeps ``num_experts`` outputs, the stacked
    weights are the ``count`` experts of ``held=(first, count)``, a shared
    SwiGLU expert of ``shared_width`` every token passes, and, under
    ``scoring="sigmoid"``, the selection bias ``select_bias`` [E], which
    the step never changes; ``n_group``, ``topk_group`` and
    ``routed_scaling`` as ``held_moe`` takes them.

    ``stats`` as ``DroplessMoEMLP``'s, of the experts held: ``moe/rows``
    [count], ``moe/load_max``, ``moe/assigned`` (the assignments to the
    experts held here, as there: all of this layer's rows when none is
    dropped) and ``moe/routed``, all that were routed (tokens x
    ``top_k``), most of them to experts elsewhere."""

    def __init__(self, hidden_size: int, expert_width: int,
                 num_experts: int, top_k: int, held,
                 initializer_range: float = 0.02,
                 out_initializer_range: float = 0.02,
                 scoring: str = "sigmoid", select_bias_range: float = 0.0,
                 shared_width: int = 0, n_group: int = 1,
                 topk_group: int = 1, routed_scaling: float = 1.0):
        super().__init__()
        init = I.Normal(0.0, initializer_range)
        out_init = I.Normal(0.0, out_initializer_range)
        e, h, f = num_experts, hidden_size, expert_width
        self.num_experts, self.top_k = e, top_k
        self.held, self.scoring = tuple(held), scoring
        self.groups = (n_group, topk_group, float(routed_scaling))

        def new(name, shape, initializer=init):
            setattr(self, name, self.create_parameter(
                shape, default_initializer=initializer))

        new("gate", [h, e])
        new("w_gate", [held[1], h, f])
        new("w_up", [held[1], h, f])
        new("w_down", [held[1], f, h], out_init)
        self.param_shardings = {
            "gate": P(), "w_gate": P("ep", None, None),
            "w_up": P("ep", None, None), "w_down": P("ep", None, None)}
        self.shared = bool(shared_width)
        if shared_width:
            new("shared_gate", [h, shared_width])
            new("shared_up", [h, shared_width])
            new("shared_down", [shared_width, h], out_init)
        self.select_bias = None
        if scoring == "sigmoid":
            new("select_bias", [e], I.Normal(0.0, select_bias_range))
            # a parameter, so that the trainer stacks it a block like the
            # rest; at learning rate 0 neither update nor decay moves it
            self.select_bias.optimize_attr["learning_rate"] = 0.0
        self.stats = {}

    def options(self, weights: dict) -> dict:
        """What ``held_moe`` is given beyond the stacked experts, from
        this layer's values by their names."""
        n_group, topk_group, routed_scaling = self.groups
        return {"held": self.held, "scoring": self.scoring,
                "n_group": n_group, "topk_group": topk_group,
                "routed_scaling": routed_scaling,
                "select_bias": weights.get("select_bias"),
                "shared": tuple(weights["shared_" + k]
                                for k in ("gate", "up", "down"))
                if self.shared else None}

    def forward(self, x):
        b, s, h = x.shape[0], x.shape[1], x.shape[2]
        names, tensors = zip(*self.named_parameters())

        def f(xv, *values):
            w = dict(zip(names, values))
            y, rows = held_moe(
                xv.reshape(b * s, h), w["gate"], w["w_gate"], w["w_up"],
                w["w_down"], self.top_k, **self.options(w))
            rows = rows.astype(jnp.float32)
            return (y.reshape(b, s, h), rows, jnp.max(rows), jnp.sum(rows),
                    jnp.float32(b * s * self.top_k))

        y, rows, load_max, assigned, routed = apply(
            f, x, *tensors, name="held_moe")
        self.stats = {"moe/rows": rows, "moe/load_max": load_max,
                      "moe/assigned": assigned, "moe/routed": routed}
        return y


class MoEMLP(nn.Layer):
    """Drop-in MoE replacement for a transformer FFN block.

    forward(x [B, S, H]) -> [B, S, H]; the load-balance loss of the last
    forward is at ``self.aux_loss`` (Tensor scalar).
    """

    def __init__(self, hidden_size: int, ffn_hidden_size: int,
                 num_experts: int, top_k: int = 1,
                 capacity_factor: float = 1.25,
                 initializer_range: float = 0.02):
        super().__init__()
        init = I.Normal(0.0, initializer_range)
        zeros = I.Constant(0.0)
        e, h, f = num_experts, hidden_size, ffn_hidden_size
        self.num_experts = e
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.gate = self.create_parameter([h, e], default_initializer=init)
        self.w_in = self.create_parameter([e, h, f],
                                          default_initializer=init)
        self.b_in = self.create_parameter([e, f],
                                          default_initializer=zeros)
        self.w_out = self.create_parameter([e, f, h],
                                           default_initializer=init)
        self.b_out = self.create_parameter([e, h],
                                           default_initializer=zeros)
        # expert dim sharded over 'ep' (strategy compiler consumes these)
        self.param_shardings = {
            "gate": P(), "w_in": P("ep", None, None),
            "b_in": P("ep", None), "w_out": P("ep", None, None),
            "b_out": P("ep", None)}
        self._aux = Tensor(jnp.zeros((), jnp.float32))

    def forward(self, x):
        b, s, h = x.shape[0], x.shape[1], x.shape[2]

        def f(xv, gw, wi, bi, wo, bo):
            y, aux = switch_moe(
                xv.reshape(b * s, h), gw, wi, bi, wo, bo,
                top_k=self.top_k, capacity_factor=self.capacity_factor)
            return y.reshape(b, s, h), aux

        out = apply(f, x, self.gate, self.w_in, self.b_in, self.w_out,
                    self.b_out, name="moe_mlp")
        y, aux = out
        self._aux = aux
        return y

    @property
    def aux_loss(self):
        """Load-balance loss of the last forward. Inside the same trace
        (GPT.loss under jit) this is the traced value; reading a value
        LEFT OVER from a finished compiled step eagerly is an error —
        raise a clear message instead of jax's UnexpectedTracerError."""
        try:  # private jax API; on a rename fall back to jax's own error
            from jax._src.core import trace_state_clean
        except ImportError:
            def trace_state_clean():
                return False

        v = self._aux
        if isinstance(v._value, jax.core.Tracer) and trace_state_clean():
            raise RuntimeError(
                "MoEMLP.aux_loss of the last compiled step is not "
                "readable eagerly: the value lived inside the jit trace. "
                "Fold it into the jitted loss (models/gpt.py GPT.loss "
                "does) or run the layer eagerly.")
        return v

    @aux_loss.setter
    def aux_loss(self, v):
        self._aux = v
