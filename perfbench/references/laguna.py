"""The plain reference of Laguna for the benchmark's check: a copy of
``paddle_tpu/models/laguna_reference.py`` (tests/perfbench pins the two equal
from the equations on), kept under ``perfbench/`` so that the comparison that
decides ``correct`` imports none of the program's code. Straight
``jax.numpy`` in float32 at ``highest`` matmul precision: one full causal
forward over prompt and output together, attention a key/value head and a
block of queries at a time, an expert at a time, no cache, no pages, no
kernel, no batching. It takes the weights the system holds, by the names the
program gives them.

With ``N(.)`` an RMSNorm with its own weight (eps 1e-6), every matrix without
bias, layer ``l`` of kind ``layer_types[l]`` with ``NH_l =
num_attention_heads_per_layer[l]`` query heads over ``KVH`` key/value heads
of ``D`` (query head ``j`` reads key/value head ``j // (NH_l / KVH)``)::

    x      = N_1(h)
    q, k, v = x W_qkv                       # [NH_l D | KVH D | KVH D]
    g      = sigmoid(x W_g)                 # [NH_l]
    full_attention:     R_yarn on the first D / 2 columns of a head (cos and
                        sin times attention_factor), the others pass;
                        o_j(t) = sum_{s <= t} softmax_s(q_j(t) . k(s) / sqrt(D)) v(s)
    sliding_attention:  R on all D columns at its own theta;
                        the sum over t - sliding_window < s <= t
    h'     = h + [g_1 o_1 .. g_NH o_NH] W_o
    y      = N_2(h')
    dense:   h'' = h' + (SiLU(y W_gate) * (y W_up)) W_down
    sparse:  sc = sigmoid(y W_r); C = the num_experts_per_tok largest;
             w_e = moe_routed_scaling_factor sc_e / sum_{C} sc
             h'' = h' + sum_{e in C, e held} w_e E_e(y) + S(y)
    logits = N_f(h) W_head

``R`` is rotary in the rotate-half convention. YaRN's frequencies over ``d``
rotated columns: ``f_j = theta^(-2j/d)``, a ramp from 0 at pair ``low`` to 1
at ``high`` (``floor`` / ``ceil`` of ``d ln(orig / (2 pi beta)) / (2 ln
theta)`` at ``beta_fast`` / ``beta_slow``, clipped to ``[0, d - 1]``),
``f_j (1 - ramp_j) + f_j / factor ramp_j``. **Departures from the published
description**: none in the equations; what the description leaves open is
read as the configuration file's ``assumed`` says. The cut (six layers, a
share of the experts, a slice of the vocabulary) is the caller's: ``layers``
yields as many layers as are served, ``held = (first, count)`` names the
experts whose weights a sparse layer holds, the head has the columns it has.

``control`` names a wrong model, for the checks that must tell it from the
right one: ``"no_gate"``, ``"no_window"`` (the sliding layers see their whole
context), ``"no_yarn"`` (the full layers' frequencies unscaled),
``"full_rotary"`` (the full layers turn all of a head), ``"no_attention_
factor"``, ``"softmax_router"`` (scores by softmax over all experts,
renormalised over the chosen), ``"no_routed_scaling"``, ``"no_shared"`` and
``"fp8_kv"`` (keys and values rounded to e4m3, a precision below the pages'
bfloat16). fp8 weights are the caller's rounding of what it passes.
"""
from __future__ import annotations

import functools
import math

import numpy as np

CONTROLS = (None, "no_gate", "no_window", "no_yarn", "full_rotary",
            "no_attention_factor", "softmax_router", "no_routed_scaling",
            "no_shared", "fp8_kv")

FULL, SLIDING = "full_attention", "sliding_attention"

#: queries of one block of the attention, columns of the dense SwiGLU and of
#: the head a product takes: a check runs beside an engine that fills the chip
_QUERY_BLOCK, _FFN_BLOCK, _HEAD_BLOCK = 512, 2048, 8192
#: a key/value head's scores are ``[NH_l / KVH, block, s]`` float32: the block
#: halves until they are no more than nine heads' block of 512 over 8,192
#: positions (17 k positions: 128 queries under 9 heads, 256 under 6)
_SCORES = 9 * 512 * 8192


def query_block(per: int, s: int) -> int:
    block = _QUERY_BLOCK
    while block > 8 and per * block * s > _SCORES:
        block //= 2
    return block


def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def inv_freq(kind: str, config: dict, control=None):
    """``(inv_freq [d / 2] float64, cos and sin's scale, d)`` of a layer
    kind's rotary embedding, ``d`` the columns of a head it turns."""
    r = config["rope_parameters"][kind]
    part = r.get("partial_rotary_factor", 1)
    if kind == FULL and control == "full_rotary":
        part = 1
    d = int(config["head_dim"] * part)
    theta = float(r["rope_theta"])
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if r["rope_type"] != "yarn":
        return f, 1.0, d
    scale = 1.0 if control == "no_attention_factor" else float(
        r.get("attention_factor", 0.1 * math.log(r["factor"]) + 1.0))
    if control == "no_yarn":
        return f, scale, d
    orig = r["original_max_position_embeddings"]
    at = lambda n: d * math.log(orig / (n * 2 * math.pi)) \
        / (2 * math.log(theta))                             # noqa: E731
    low = max(math.floor(at(r["beta_fast"])), 0)
    high = min(math.ceil(at(r["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / float(r["factor"]) * ramp, scale, d


def _e4m3(x):
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


@functools.lru_cache(maxsize=None)
def _attn_fns(eps: float, window, heads: tuple, rope: tuple, gated: bool,
              e4m3: bool):
    """A layer's attention in pieces that each hold little (a check runs
    beside an engine that fills the chip): the norm, the keys and values of
    every position, and a block of queries through its softmax to what it
    adds to the residual. ``window``: None for a layer that sees its whole
    context; ``heads`` ``(NH_l, KVH, D)``; ``rope`` ``(inv_freq, scale,
    d)``."""
    import jax
    import jax.numpy as jnp

    nh, kvh, hd = heads
    inv, scale, d = rope
    # a column's frequency: pair ``(j, j + d / 2)`` shares ``inv[j]``
    freq = np.concatenate([inv, inv, np.zeros(hd - d)]).astype(np.float32)
    # rotate-half as a product: ``x @ half`` is ``[-x[d/2:d] | x[:d/2] | 0]``
    # (a concatenation inside a head's 128 lanes is what XLA:TPU's fusion
    # emitter aborts on: "IsFusibleUnalignedDUS", my chip run, PR 57)
    half = np.zeros((hd, hd), np.float32)
    for j in range(d // 2):
        half[j + d // 2, j], half[j, j + d // 2] = -1.0, 1.0
    turned = (np.arange(hd) < d)[None, :]

    def turn(x, t0):
        """Rotate-half rotary over the first ``d`` columns of ``x`` [b,
        heads, D] at positions ``t0 + i``; the others pass."""
        pos = (t0 + jnp.arange(x.shape[0])).astype(jnp.float32)
        ang = pos[:, None] * freq[None, :]
        cos = jnp.where(turned, jnp.cos(ang) * scale, 1.0)[:, None, :]
        sin = jnp.where(turned, jnp.sin(ang) * scale, 0.0)[:, None, :]
        return x * cos + jnp.einsum("thd,de->the", x, half) * sin

    def keys_values(n, w_k, w_v):
        s = n.shape[0]
        k = turn((n @ w_k).reshape(s, kvh, hd), 0)
        v = (n @ w_v).reshape(s, kvh, hd)
        return (_e4m3(k), _e4m3(v)) if e4m3 else (k, v)

    def queries(n_blk, w_q, t0):
        return turn((n_blk @ w_q).reshape(n_blk.shape[0], nh, hd), t0)

    def head(q, k, v, t0):
        """q [b, per, D] at positions ``t0 + i`` over k, v [s, D]."""
        t = t0 + jnp.arange(q.shape[0])[:, None]
        j = jnp.arange(k.shape[0])[None, :]
        seen = j <= t
        if window is not None:
            seen = seen & (j > t - window)
        att = jnp.einsum("tgd,sd->gts", q, k) / np.sqrt(hd)
        w = jax.nn.softmax(jnp.where(seen[None], att, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", w, v)

    def out(x_blk, n_blk, o, w_g, w_o):
        """o [b, NH, D], gated a head, through the way out."""
        if gated:
            o = o * jax.nn.sigmoid(n_blk @ w_g)[..., None]
        return x_blk + o.reshape(o.shape[0], nh * hd) @ w_o

    return (jax.jit(lambda h, w: rms_norm(h, w, eps)), jax.jit(keys_values),
            jax.jit(queries), jax.jit(head), jax.jit(out))


@functools.lru_cache(maxsize=None)
def _ffn_fns():
    import jax

    swiglu = jax.jit(lambda y, gate, up, down: (
        jax.nn.silu(y @ gate) * (y @ up)) @ down)
    weighted = jax.jit(lambda acc, y, gate, up, down, w: acc + w[:, None] * (
        (jax.nn.silu(y @ gate) * (y @ up)) @ down))
    return swiglu, weighted


def route(y, router, config: dict, control=None):
    """``(chosen [s, K] int32, weights [s, K] float32)``: the
    ``num_experts_per_tok`` largest of the router's scores over all experts
    and the weights their experts' outputs take."""
    import jax
    import jax.numpy as jnp

    logits = y @ router
    score = jax.nn.softmax(logits, -1) if control == "softmax_router" \
        else jax.nn.sigmoid(logits)
    top, chosen = jax.lax.top_k(score, config["num_experts_per_tok"])
    w = top / jnp.sum(top, -1, keepdims=True)
    if control != "no_routed_scaling":
        w = w * config["moe_routed_scaling_factor"]
    return chosen.astype(jnp.int32), w


def moe(y, w: dict, config: dict, held, control=None):
    """The sparse layer's output over ``y`` [s, h] -> ``(sum over the held
    chosen experts + the shared expert, chosen [s, K], rows [count])``:
    ``held = (first, count)``, ``w["ffn.w_*"]`` ``[count, ...]``."""
    import jax.numpy as jnp

    swiglu, weighted = _ffn_fns()
    first, count = held
    chosen, weight = route(y, _f32(w["ffn.gate"]), config, control)
    out = jnp.zeros_like(y)
    rows = []
    # (asked for once a layer: a caller's mapping may round what it hands out)
    gates, ups, downs = w["ffn.w_gate"], w["ffn.w_up"], w["ffn.w_down"]
    for e in range(count):
        mine = chosen == first + e
        rows.append(int(jnp.sum(mine)))
        out = weighted(out, y, _f32(gates[e]), _f32(ups[e]), _f32(downs[e]),
                       jnp.sum(jnp.where(mine, weight, 0.0), -1))
    del gates, ups, downs
    if control != "no_shared":
        out = out + swiglu(y, _f32(w["ffn.shared_gate"]),
                           _f32(w["ffn.shared_up"]),
                           _f32(w["ffn.shared_down"]))
    return out, chosen, np.asarray(rows, np.int64)


def forward(layers, other: dict, tokens, config: dict, held=None,
            control=None, keep=None) -> dict:
    """The full causal forward over ``tokens`` [s]. ``layers`` yields one
    layer's weights at a time by the names the program gives them; ``other``
    holds the embedding, the final norm and the head; ``config`` the sizes
    under the keys of ``config.json`` (``layer_types``, ``mlp_layer_types``
    and ``num_attention_heads_per_layer`` a served layer each); ``held`` the
    experts whose weights the sparse layers hold (default: all). Returns
    float32 ``state`` [s, h] (what the head reads); ``keys``, ``values``,
    one ``[s, KVH, D]`` a layer, the rotated keys and the values as a cache
    would hold them; ``routed``, one ``[s, K]`` int32 a sparse layer, the
    experts each position chose; ``rows``, one ``[count]`` a sparse layer,
    the positions each held expert was given. ``layers`` may end early (the
    state is then that layer's); ``keep`` names the layers whose keys and
    values are wanted (default: all), the others' entries are ``None``: at
    17 k positions a layer's are 140 MB."""
    import jax
    import jax.numpy as jnp

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    tokens = np.asarray(tokens).reshape(-1)
    s = len(tokens)
    eps, hd, kvh = (config["rms_norm_eps"], config["head_dim"],
                    config["num_key_value_heads"])
    held = (0, config["num_experts"]) if held is None else tuple(held)
    swiglu, _ = _ffn_fns()
    out = {"keys": [], "values": [], "routed": [], "rows": []}
    with jax.default_matmul_precision("highest"):
        x = _f32(other["embeddings.wte.weight"][tokens])
        for i, w in enumerate(layers):
            kind = config["layer_types"][i]
            nh = config["num_attention_heads_per_layer"][i]
            per, qw, kw = nh // kvh, nh * hd, kvh * hd
            window = config["sliding_window"] if kind == SLIDING \
                and control != "no_window" else None
            inv, scale, d = inv_freq(kind, config, control)
            norm, keys_values, queries, head, way_out = _attn_fns(
                eps, window, (nh, kvh, hd), (tuple(inv.tolist()), scale, d),
                control != "no_gate", control == "fp8_kv")
            n = norm(x, _f32(w["ln_1.weight"]))
            qkv = w["attn.qkv.weight"]
            k, v = keys_values(n, _f32(qkv[:, qw:qw + kw]),
                               _f32(qkv[:, qw + kw:]))
            kept = keep is None or i in keep
            out["keys"].append(k if kept else None)
            out["values"].append(v if kept else None)
            w_q, w_g, w_o = (_f32(qkv[:, :qw]), _f32(w["attn.gate.weight"]),
                             _f32(w["attn.o.weight"]))
            blocks, step = [], query_block(per, s)
            for t0 in range(0, s, step):            # a block of queries,
                rows = slice(t0, t0 + step)             # a key/value head
                q = queries(n[rows], w_q, np.int32(t0))     # at a time
                o = [head(q[:, j * per:(j + 1) * per], k[:, j], v[:, j],
                          np.int32(t0)).block_until_ready()
                     for j in range(kvh)]
                blocks.append(way_out(x[rows], n[rows],
                                      jnp.concatenate(o, axis=1), w_g, w_o))
            x = jnp.concatenate(blocks, 0)
            del w_q, w_g, w_o, blocks, n, k, v, q, o
            y = norm(x, _f32(w["ln_2.weight"]))
            if config["mlp_layer_types"][i] == "dense":
                gate, up, down = (w["ffn.fc_gate.weight"],
                                  w["ffn.fc_in.weight"],
                                  w["ffn.fc_out.weight"])
                for lo in range(0, gate.shape[1], _FFN_BLOCK):
                    cols = slice(lo, lo + _FFN_BLOCK)
                    x = (x + swiglu(y, _f32(gate[:, cols]), _f32(up[:, cols]),
                                    _f32(down[cols]))).block_until_ready()
            else:
                add, chosen, rows = moe(y, w, config, held, control)
                x = x + add
                out["routed"].append(np.asarray(chosen))
                out["rows"].append(rows)
        out["state"] = rms_norm(x, _f32(other["ln_f.weight"]), eps)
    return out


def _head_blocks(state, other: dict):
    """``state`` [n, h] float32 times the head, ``_HEAD_BLOCK`` columns at a
    time: ``(first column, [n, block] float32 logits)``."""
    import jax

    head = other["lm_head.weight"]
    with jax.default_matmul_precision("highest"):
        for lo in range(0, head.shape[1], _HEAD_BLOCK):
            yield lo, _f32(state) @ _f32(head[:, lo:lo + _HEAD_BLOCK])


def logits(state, other: dict):
    """``[s, vocab]`` float32 logits of ``forward``'s ``state``."""
    return np.concatenate([np.asarray(b) for _, b in _head_blocks(
        state, other)], -1)


def shortfall(state, other: dict, targets):
    """For each position of ``state`` [n, h]: how far its logit for
    ``targets`` [n] lies below its largest logit, that largest logit, and
    the standard deviation of the position's logits over the vocabulary (the
    unit a seeded model's distances are read in); each ``[n]`` float32 on
    the host."""
    targets = np.asarray(targets)
    top = np.full(targets.shape, -np.inf, np.float32)
    mine = np.zeros(targets.shape, np.float32)
    total = np.zeros(targets.shape, np.float64)
    squares = np.zeros(targets.shape, np.float64)
    width = 0
    for lo, block in _head_blocks(state, other):
        block = np.asarray(block)
        top = np.maximum(top, block.max(-1))
        here = (targets >= lo) & (targets < lo + block.shape[1])
        mine[here] = block[here, targets[here] - lo]
        total += block.sum(-1, dtype=np.float64)
        squares += np.square(block, dtype=np.float64).sum(-1)
        width += block.shape[1]
    sigma = np.sqrt(np.maximum(squares / width - (total / width) ** 2, 0.0))
    return top - mine, top, sigma.astype(np.float32)
