"""Laguna (huggingface.co/poolside/Laguna-S-2.1 config.json, ``model_type``
``laguna``), served.

Grouped-query attention of two kinds **over one set of key/value heads**:
full causal attention with ``num_attention_heads_per_layer[l]`` = 48 query
heads in one layer of four, attention under a sliding window of 512 with 72
query heads in the others, 8 key/value heads of 128 in both, **a gate a head**
on the attention's output; a dense SwiGLU in ``mlp_only_layers``, elsewhere
a sigmoid router over 256 experts (10 a token, renormalised, x 2.5) and one
shared expert. With ``N`` an RMSNorm (eps 1e-6, float32 statistics), layer
``l`` of kind ``layer_types[l]``, ``NH_l`` its query heads::

    x      = N_1(h)
    q,k,v  = x W_q [T, NH_l, 128], x W_k, x W_v [T, 8, 128]
    g      = sigmoid(x W_g)                       # [T, NH_l], a number a head
    full:     R over the first 64 of a head's 128 (partial_rotary_factor
              0.5), YaRN's frequencies (theta 5e5, factor 128), cos and sin
              times attention_factor; query t sees keys j <= t
    sliding:  R over all 128 at theta 1e4;  t - sliding_window < j <= t
    o      = softmax(R(q) R(k)^T / sqrt(128)) v ;  o[:, n] *= g[:, n]
    h'     = h + concat(o) W_o
    y      = N_2(h')
    dense:    h'' = h' + (SiLU(y W_gate) * y W_up) W_down
    sparse:   sc = sigmoid(y W_r) ; the 10 largest chosen ;
              w_e = 2.5 sc_e / sum_chosen sc ;
              h'' = h' + sum_{e chosen and held} w_e E_e(y) + S(y)
    logits = N_f(h_last) W_head

It is served over ``serving.paged_cache.WindowedKVPools`` (a ``cache_spec()``
of kind ``"windowed_kv"``): the full layers' pages keep a slot's whole
context, the windowed layers' pages live in a page space that holds only the
window (``paged_cache.WindowSpace``, the one dots3's latent windows use), and
query heads that differ by layer meet the same 8 key/value heads in
``ops/paged_attention.grouped_paged_attention`` (a key/value head's 6 or 9
query heads' queries the rows of one left operand, laid end to end and padded
once: a decode row's are one tile of 16). The expert layer holds a share of
the routed experts (``distributed/moe.held_moe``), as Ling's and dots3's do.

**The layers are unlike and the tick unrolls them** on ``tick.LayerwiseLM``,
each layer's weights its own arrays. Like ``models/falcon_h1.py`` the forward
cuts itself ``before`` / the calls on the pools / ``after`` around
``TickRows.dense``: a tick without a chunk multiplies no pad token through a
matrix and routes none.

``models/laguna_reference.py`` is the plain float32 reference of the same
equations; it reads this model's weights by the names given here and none of
its code. There is no training forward.

What ``config.json`` does not settle, and how it is read here (the
configuration file's ``assumed``, each a control of the reference): the gate
is a sigmoid of a projection of the normed layer input, a number a head and
token, on the attention's output before ``W_o``; the router scores by
sigmoid, with no selection bias and no group limit, and the shared expert is
ungated; no QK norm; YaRN's ramp is ``models/deepseek_v2.yarn_bounds``' over
the rotated 64 and ``attention_factor`` multiplies the rotated half alone;
``initializer_range`` 0.02; q, k and v are one matrix's columns (storage).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..distributed.moe import HeldMoEMLP, held_moe
from ..nn import initializer as I
from ..profiler.trace import annotate
from .deepseek_v2 import rope_by_table, yarn_table
from .olmo_hybrid import TickRecord as _HybridRecord
from .tick import (HeldExpertsConfig, LayerwiseLM, SwiGLUMLP, TickRows,
                   Weight, rms)

__all__ = ["LagunaConfig", "Laguna", "laguna_ragged_apply", "TickRecord",
           "TICK_STATS"]

_F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"

#: what one tick reports beside its tokens, in this order (``aux["stats"]``):
#: the rows and tokens it carried, the keys its decode rows and its chunk
#: rows read and the query-key pairs they scored in a full layer and (at most
#: ``sliding_window`` a query) in a windowed one, then, means over the expert
#: layers, what the live tokens gave the held experts
TICK_STATS = ("decode_rows", "chunk_tokens", "decode_keys", "chunk_keys",
              "chunk_pairs", "window_decode_keys", "window_chunk_keys",
              "window_chunk_pairs", "expert_rows",
              "expert_load_max_over_mean", "experts_touched_share",
              "held_rows_unaccounted")

#: the most rows of a key/value head's left operand (``G t``: its query
#: heads' queries end to end, which fill whole tiles at every piece's ``t``)
#: one call of the attention takes: the kernel keeps a row's scores for every
#: key/value head in VMEM; a chunk row attends in pieces of so many queries
_ATTN_ROWS = 288

ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
           "original_max_position_embeddings": 8192, "beta_slow": 1,
           "beta_fast": 32, "attention_factor": 1.4852030263919618,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1}}


@dataclass
class LagunaConfig(HeldExpertsConfig):
    """Sizes under the names of the model's ``config.json``."""
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    moe_router_logit_softcapping: float = 0
    moe_apply_router_weight_on_input: bool = False
    sliding_window: int = 512
    gating: str = "per-head"
    rope_parameters: dict = field(
        default_factory=lambda: {k: dict(v) for k, v in ROPE.items()})
    #: a layer's kind, FFN and query heads; None: the published period (one
    #: full layer of 48 heads, then three sliding ones of 72; layer 0 dense)
    layer_types: Optional[Tuple[str, ...]] = None
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    num_attention_heads_per_layer: Optional[Tuple[int, ...]] = None
    initializer_range: float = 0.02
    #: (first, count): the routed experts held here; None: all of them
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = tuple(SLIDING if i % 4 else FULL
                                     for i in range(n))
        if self.mlp_layer_types is None:
            self.mlp_layer_types = tuple("sparse" if i else "dense"
                                         for i in range(n))
        if self.num_attention_heads_per_layer is None:
            self.num_attention_heads_per_layer = tuple(
                self.num_attention_heads if kind == FULL
                else self.num_attention_heads * 3 // 2
                for kind in self.layer_types)
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            setattr(self, name, tuple(getattr(self, name)))
            if len(getattr(self, name)) != n:
                raise ValueError(f"{name} names {len(getattr(self, name))} "
                                 f"layers, num_hidden_layers {n}")
        if set(self.layer_types) - {FULL, SLIDING} \
                or set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError("layer_types are full_attention or "
                             "sliding_attention, mlp_layer_types dense or "
                             "sparse")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError("query heads that do not divide into "
                             f"{self.num_key_value_heads} key/value heads")
        if self.gating != "per-head" or self.moe_router_logit_softcapping \
                or self.moe_apply_router_weight_on_input \
                or not self.norm_topk_prob:
            raise NotImplementedError(
                "a gate a head, no soft cap on the router's logits, the "
                "weights renormalised and on the experts' outputs are what "
                "this model serves")

    @property
    def n_routed_experts(self) -> int:      # ``HeldExpertsConfig``'s name
        return self.num_experts

    def is_moe(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == "sparse"

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    def q_width(self, layer: int) -> int:
        return self.num_attention_heads_per_layer[layer] * self.head_dim

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    def rotary(self, kind: str):
        """``(inv_freq, cos_sin_scale)`` of a layer kind's RoPE: the table
        spans ``partial_rotary_factor`` of a head (``rope_by_table`` turns as
        many columns as the table has pairs and passes the rest)."""
        r = self.rope_parameters[kind]
        dim = int(self.head_dim * r.get("partial_rotary_factor", 1))
        inv, scale, _ = yarn_table(dim, float(r["rope_theta"]),
                                   r if r["rope_type"] == "yarn" else None)
        return inv, float(scale)

    def layer_params(self, layer: int) -> int:
        """Parameters of one layer as held here (the held experts alone)."""
        h, nh = self.hidden_size, self.num_attention_heads_per_layer[layer]
        n = h * (2 * self.q_width(layer) + 2 * self.kv_width + nh) + 2 * h
        if not self.is_moe(layer):
            return n + 3 * h * self.intermediate_size
        return n + h * self.num_experts + self.num_experts + 3 * h * (
            self.moe_intermediate_size * self.held[1]
            + self.shared_expert_intermediate_size)

    @staticmethod
    def laguna_s():
        """The catalog row: 48 layers, 256 experts, 100,352 words."""
        return LagunaConfig()

    @staticmethod
    def tiny(**kw):
        """Unit-test sizes: five layers (full and dense, two sliding, full,
        sliding) of hidden 32, 4 and 6 query heads over 2 key/value heads of
        16, a window of 6, 8 experts of which a token takes 3."""
        rope = {FULL: dict(ROPE[FULL], rope_theta=100.0, factor=8,
                           original_max_position_embeddings=16,
                           attention_factor=1.2079441541679836),
                SLIDING: dict(ROPE[SLIDING], rope_theta=50.0)}
        base = dict(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            num_hidden_layers=5, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, max_position_embeddings=128,
            num_experts=8, num_experts_per_tok=3, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, sliding_window=6,
            rope_parameters=rope, initializer_range=0.2,
            layer_types=(FULL, SLIDING, SLIDING, FULL, SLIDING))
        base.update(kw)
        return LagunaConfig(**base)


class GatedAttention(nn.Layer):
    """The weights of a layer's attention: ``[q | k | v]`` one matrix, the
    gate's projection (a column a query head) and the way out."""

    def __init__(self, c: LagunaConfig, layer: int):
        super().__init__()
        init = I.Normal(0.0, c.initializer_range)
        qw = c.q_width(layer)
        self.qkv = Weight([c.hidden_size, qw + 2 * c.kv_width], init)
        self.gate = Weight(
            [c.hidden_size, c.num_attention_heads_per_layer[layer]], init)
        self.o = Weight([qw, c.hidden_size], init)


class LagunaBlock(nn.Layer):
    def __init__(self, c: LagunaConfig, layer: int):
        super().__init__()
        one = I.Constant(1.0)
        self.ln_1 = Weight([c.hidden_size], one)
        self.attn = GatedAttention(c, layer)
        self.ln_2 = Weight([c.hidden_size], one)
        if c.is_moe(layer):
            self.ffn = HeldMoEMLP(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_per_tok, c.held,
                initializer_range=c.initializer_range,
                out_initializer_range=c.initializer_range,
                scoring="sigmoid",
                shared_width=c.shared_expert_intermediate_size,
                routed_scaling=c.moe_routed_scaling_factor)
        else:
            self.ffn = SwiGLUMLP(c)


class TickRecord(_HybridRecord):
    """This model's ticks (``aux`` of ``laguna_ragged_apply``):
    ``models/olmo_hybrid.TickRecord`` (every drained tick's ``stats`` in the
    registry, for the requests a caller watches the largest logit of each
    row that chose a token, and for every request **where its latest token's
    row stood**: after that position the slot's pages hold its keys and all
    before it) and, for the watched, the experts each such row chose."""

    STATS = TICK_STATS

    def __init__(self):
        super().__init__()
        self._routed: dict = {}

    def tick(self, aux: dict, positions, rids):
        note_top = super().tick(aux, positions, rids)
        routed = np.asarray(aux["routed"]) \
            if any(self.watch(rid) for rid in rids) else None

        def note(rid: int, row: int) -> None:
            note_top(rid, row)
            if routed is not None and self.watch(rid):
                self._routed.setdefault(rid, []).append(routed[:, row])

        return note

    def forget(self, keep) -> None:
        super().forget(keep)
        self._routed = {r: v for r, v in self._routed.items() if r in keep}

    def routed_experts(self, rid: int):
        """``[tokens, expert layers, top_k]`` int32: the experts the row
        that chose each of request ``rid``'s tokens was routed to."""
        return np.stack(self._routed[rid])


class Laguna(LayerwiseLM):
    """The served model: ``LayerwiseLM``'s weights, what caches it keeps and
    the tick's forward."""

    def __init__(self, config: LagunaConfig):
        super().__init__(config, LagunaBlock)

    # -- what ServingEngine asks of a model (models/tick.py) -------------
    def cache_spec(self) -> dict:
        c = self.config
        return {"kind": "windowed_kv",
                "full_layers": len(c.layers_of(FULL)),
                "window_layers": len(c.layers_of(SLIDING)),
                "window": c.sliding_window,
                "key_value_heads": c.num_key_value_heads,
                "head_dim": c.head_dim, "tick_record": TickRecord}

    def ragged_apply(self, stacked, other, pools, tokens, tok_pos, tok_limit,
                     row_tab, row_pos0, row_len, sample_ix, *, decode_rows,
                     chunk_width, has_chunks=None):
        return laguna_ragged_apply(
            self.config, stacked, other, pools, tokens, tok_pos, tok_limit,
            row_tab, row_pos0, row_len, sample_ix, decode_rows, chunk_width,
            has_chunks=has_chunks)


# --------------------------------------------------------------------------
# the tick's forward
# --------------------------------------------------------------------------
def _pieces(t: int, group: int) -> int:
    """In how many pieces of queries a row of ``t`` attends so that a
    key/value head's operand has at most ``_ATTN_ROWS`` rows."""
    n = 1
    while t % (2 * n) == 0 and group * (t // n) > _ATTN_ROWS:
        n *= 2
    return n


def laguna_ragged_apply(c: LagunaConfig, stacked, other, pools, tokens,
                        tok_pos, tok_limit, row_tab, row_pos0, row_len,
                        sample_ix, decode_rows: int, chunk_width: int,
                        has_chunks=None):
    """Mixed prefill/decode forward over ``WindowedKVPools``: the arguments
    of ``models/gpt.gpt_ragged_apply``, ``row_tab`` the pair ``(the full
    layers' page tables, the windowed layers')``, both ``[R, NPs]``, that
    ``WindowedKVPagePool.row_tables`` gives.

    **``has_chunks``** goes to ``TickRows.dense`` as in
    ``models/falcon_h1.falcon_h1_ragged_apply``: what a layer does to its
    rows before the pools (``before``: ``ln_1``, ``[q | k | v]``, the gate,
    RoPE) and after them (``after``: the gate's multiply, ``W_o``, the dense
    SwiGLU or the held experts) is row-wise, so one layer's ``after`` and the
    next one's ``before`` are one stretch that a tick without a chunk runs
    over its decode rows alone: no pad token is multiplied or routed.

    Returns ``(logits [S, V], pools, aux)``: ``aux["stats"]`` float32 in
    ``TICK_STATS``' order, ``aux["top_logit"]`` ``[S]`` float32 the sampled
    rows' largest logit and ``aux["routed"]`` ``[expert layers, S, top_k]``
    int32 the experts each sampled row chose."""
    tab, wtab = row_tab
    nt, nd, w = tokens.shape[0], decode_rows, chunk_width
    ps, nps = pools.page_size, tab.shape[1]
    eps, kvh, hd = c.rms_norm_eps, c.num_key_value_heads, c.head_dim
    kw, window, top_k = c.kv_width, c.sliding_window, c.num_experts_per_tok
    first, count = c.held
    rope = {kind: c.rotary(kind) for kind in (FULL, SLIDING)}
    with annotate("tick/embed"):
        x = other["embeddings.wte.weight"][tokens]              # [NT, h]
    rows_ = TickRows(ps, nps, tok_pos, tok_limit, row_pos0, nt, nd, w,
                     has_chunks)
    page, wpage = rows_.page_of(tab), rows_.page_of(wtab)
    off = tok_pos % ps
    wrote, wwrote = rows_.touched(page, tab), rows_.touched(wpage, wtab)
    live = rows_.live(tab, row_len)
    on = (row_len > 0) & (tab[:, 0] > 0)
    keys = jnp.where(on, jnp.minimum(row_pos0 + row_len, nps * ps),
                     0).astype(_F32)
    pairs = jnp.where(live, tok_pos + 1, 0).astype(_F32)
    wkeys = jnp.where(on, jnp.minimum(
        row_pos0 + row_len, window - 1 + row_len), 0).astype(_F32)
    wpairs = jnp.minimum(pairs, float(window))
    stats = [jnp.sum(on[:nd]).astype(_F32), jnp.sum(row_len[nd:] * on[nd:]
                                                    ).astype(_F32),
             jnp.sum(keys[:nd]), jnp.sum(keys[nd:]), jnp.sum(pairs[nd:]),
             jnp.sum(wkeys[:nd]), jnp.sum(wkeys[nd:]), jnp.sum(wpairs[nd:])]

    def before(i, p, x, pos):
        """What layer ``i`` makes of its rows before it touches the pools."""
        nh, qw = c.num_attention_heads_per_layer[i], c.q_width(i)
        inv, scale = rope[c.layer_types[i]]
        with annotate("blk/qkv"):
            n = rms(x, p["ln_1.weight"], eps)
            qkv = n @ p["attn.qkv.weight"]
            gate = jax.nn.sigmoid((n @ p["attn.gate.weight"]).astype(_F32))
            turn = lambda a, heads: rope_by_table(          # noqa: E731
                a.reshape(-1, heads, hd), pos, inv, scale)
            return (turn(qkv[:, :qw], nh), turn(qkv[:, qw:qw + kw], kvh),
                    qkv[:, qw + kw:].reshape(-1, kvh, hd), gate)

    def attention(pl, i, at, q, k, v):
        """Layer ``i``, the ``at``-th of its kind: its keys and values into
        its pages, then every row against them."""
        nh = c.num_attention_heads_per_layer[i]
        sliding = c.layer_types[i] == SLIDING
        with annotate("blk/kv_scatter"):
            k, v = k[:, None], v[:, None]
            pl = pl.scatter_window(at, wpage, off, k, v, wwrote) if sliding \
                else pl.scatter(at, page, off, k, v, wrote)
        table = wtab if sliding else tab

        def attend(rows, cut):
            # a chunk row attends in pieces of queries (a later piece sees
            # the earlier ones' keys: all are written)
            n_, t = cut.n, cut.t
            pieces = _pieces(t, nh // kvh)
            size = t // pieces
            lead = jnp.tile(jnp.arange(pieces, dtype=jnp.int32) * size, n_)
            rep = lambda a: jnp.repeat(a[rows], pieces, axis=0)  # noqa: E731
            with annotate("blk/attn/window" if sliding else "blk/attn/full"):
                meta = (rep(table), rep(row_pos0) + lead,
                        jnp.clip(rep(row_len) - lead, 0, size))
                qq = cut(q).reshape(n_ * pieces, size, nh, hd)
                o = pl.attend_window(at, qq, *meta, window) if sliding \
                    else pl.attend(at, qq, *meta)
                return cut.flat(o.reshape(n_, t, nh, hd))

        return rows_.groups(attend, join=False), pl

    def after(i, p, x, o, gate, live):
        """What layer ``i`` makes of its rows once the pools have answered
        -> ``(x, what it says of its experts [n, 4 + top_k])``."""
        with annotate("blk/attn_out"):
            o = o * gate[..., None].astype(o.dtype)
            x = x + o.reshape(o.shape[0], -1).astype(x.dtype) \
                @ p["attn.o.weight"]
        with annotate("blk/ffn"):
            y = rms(x, p["ln_2.weight"], eps)
            if not c.is_moe(i):
                mid = jax.nn.silu(y @ p["ffn.fc_gate.weight"]) \
                    * (y @ p["ffn.fc_in.weight"])
                return x + mid @ p["ffn.fc_out.weight"], \
                    jnp.zeros((x.shape[0], 0), _F32)
            out, rows = held_moe(
                y, p["ffn.gate"], p["ffn.w_gate"], p["ffn.w_up"],
                p["ffn.w_down"], top_k, c.held, scoring="sigmoid",
                shared=(p["ffn.shared_gate"], p["ffn.shared_up"],
                        p["ffn.shared_down"]),
                routed_scaling=c.moe_routed_scaling_factor)
            with annotate("moe/route"):
                # what the tick says of its routing: held_moe's own rule
                # again on the scores (small beside the experts), of the
                # live tokens' rows; and how far the rows held_moe gave out
                # lie from these tokens' choices of held experts (0)
                score = jax.nn.sigmoid(jnp.dot(
                    y, p["ffn.gate"].astype(y.dtype),
                    preferred_element_type=_F32))
                chosen = jax.lax.top_k(score, top_k)[1].astype(jnp.int32)
                held_ = (chosen >= first) & (chosen < first + count)
                lost = jnp.abs(jnp.sum(rows) - jnp.sum(held_)).astype(_F32)
                mine = jnp.sum(
                    (chosen[:, :, None] - first == jnp.arange(
                        count, dtype=jnp.int32)) & live[:, None, None],
                    (0, 1)).astype(_F32)                        # [count]
                said = jnp.stack([
                    jnp.sum(mine), jnp.max(mine) / jnp.maximum(
                        jnp.mean(mine), 1e-9), jnp.mean(mine > 0), lost])
            # (a statistic of the layer rides as a column of its rows: what
            # leaves ``TickRows.dense`` is per-token arrays)
            return x + out.astype(x.dtype), jnp.concatenate([
                jnp.broadcast_to(said, (x.shape[0], 4)),
                chosen.astype(_F32)], -1)

    def between(i, p, nxt, x, o, gate, live, pos):
        x, said = after(i, p, x, o, gate, live)
        return x, said, before(i + 1, nxt, x, pos)

    layers = [stacked[f"layer{i}"] for i in range(c.num_hidden_layers)]
    nth = {FULL: 0, SLIDING: 0}
    said_moe = []
    with annotate("blk/qkv"):
        q, k, v, gate = rows_.dense(partial(before, 0, layers[0]), x,
                                    tok_pos)
    for i, p in enumerate(layers):
        kind = c.layer_types[i]
        o, pools = attention(pools, i, nth[kind], q, k, v)
        nth[kind] += 1
        with annotate("blk/ffn"):
            if i + 1 < len(layers):
                x, said, (q, k, v, gate) = rows_.dense(
                    partial(between, i, p, layers[i + 1]), x, o, gate, live,
                    tok_pos)
            else:
                x, said = rows_.dense(partial(after, i, p), x, o, gate, live)
        if c.is_moe(i):
            said_moe.append(said)
    with annotate("tick/head"):
        last = rms(x[sample_ix], other["ln_f.weight"], eps)
        logits = last @ other["lm_head.weight"]                 # [S, V]
        top = jnp.max(logits.astype(_F32), -1)
    n_s = sample_ix.shape[0]
    per_moe = jnp.mean(jnp.stack([s[0, :4] for s in said_moe]), 0) \
        if said_moe else jnp.zeros((4,), _F32)
    routed = jnp.stack([s[sample_ix, 4:].astype(jnp.int32)
                        for s in said_moe]) if said_moe else \
        jnp.zeros((0, n_s, top_k), jnp.int32)
    return logits, pools, {
        "stats": jnp.concatenate([jnp.stack(stats), per_moe]),
        "top_logit": top, "routed": routed}
