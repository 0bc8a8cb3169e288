"""DeepSeek-V2 (huggingface.co/deepseek-ai/DeepSeek-V2 config.json,
``model_type`` ``deepseek_v2``; arXiv:2405.04434), served.

One kind of layer, sixty times: latent attention (MLA, section 2.1) over the
**whole** context, no indexer, no window, no gate, no rescale of the
latents, its RoPE stretched by YaRN (a frequency table, and a softmax scale
of its own); the leading ``first_k_dense_replace`` layers a dense SwiGLU, the
others DeepSeekMoE (section 2.2): a softmax router over all
``n_routed_experts``, **limited to** ``topk_group`` **of** ``n_group``
**groups of experts**, the chosen scores times ``routed_scaling_factor`` as
weights, not renormalised, and ``n_shared_experts`` shared experts, which
are one SwiGLU of their widths' sum. This model holds a share of the routed
experts (``distributed/moe.held_moe``).

It is ``models/dots3.py``'s sibling: both are served layer by layer
(``models/tick.py``: the protocol ``ServingEngine`` asks of a model, the
weights' containers, the state the engine draws, ``TickRows`` and
``TickRecord``), the pools are ``serving.paged_cache.LatentPools`` with no
indexer keys and no window space, written and read through their
``scatter_latent`` and ``attend``. What is its own is below: the
configuration, YaRN's table, the layer and the tick's forward.
``models/deepseek_v2_reference.py`` is the plain float32 reference of the
same equations; it reads this model's weights by the names given here and
none of its code. There is no training forward.

What ``config.json`` does not settle, and how it is read here: HF pairs the
rope dimensions interleaved and permutes them to halves before rotate-half;
with seeded weights that permutation of ``W_qb``'s and ``W_kva``'s rope
columns is storage, not mathematics, and the columns are taken as already in
halves. A group's score is its best expert's (``group_limited_greedy``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..distributed.moe import HeldMoEMLP, held_moe, kept_groups
from ..nn import initializer as I
from ..profiler.trace import annotate
from . import tick as _tick
from .tick import (HeldExpertsConfig, LayerwiseLM, SwiGLUMLP, TickRows,
                   Weight, rms)

#: what one tick reports beside its tokens, in this order (``aux["stats"]``)
TICK_STATS = ("group_hit_share", "expert_rows", "expert_load_max_over_mean",
              "experts_touched_share", "decode_pairs", "decode_keys",
              "chunk_pairs", "chunk_keys")

YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_bounds(dim: int, theta: float, scaling: dict) -> Tuple[int, int]:
    """``(low, high)``: the pairs between which YaRN's ramp rises, ``dim(n)
    = d ln(orig / (2 pi n)) / (2 ln theta)`` floored at ``beta_fast`` and
    ceiled at ``beta_slow`` rotations: 10 and 23 as published."""
    orig = scaling["original_max_position_embeddings"]
    at = lambda n: dim * math.log(orig / (n * 2 * math.pi)) \
        / (2 * math.log(theta))                             # noqa: E731
    return (max(math.floor(at(scaling["beta_fast"])), 0),
            min(math.ceil(at(scaling["beta_slow"])), dim - 1))


def yarn_table(dim: int, theta: float, scaling: Optional[dict]):
    """``(inv_freq [dim / 2] float32, cos_sin_scale, softmax_factor)`` of a
    rotary embedding of ``dim`` columns under ``rope_scaling`` (None: the
    plain ``theta ** (-2j / dim)``, 1, 1). YaRN, once, in float64 on the
    host: a ramp from 0 at ``low`` to 1 at ``high`` over the pairs
    (``yarn_bounds``), ``inv_freq = f (1 - ramp) + (f / factor) ramp``; cos
    and sin are multiplied by ``m(mscale) / m(mscale_all_dim)`` and the
    scores by ``m(mscale_all_dim) ** 2``, ``m(x) = 0.1 x ln(factor) + 1``.
    ``dim`` may be a part of a head (``models/laguna.py``: the rotated half;
    ``rope_by_table`` passes the rest), the kind may stand under
    ``rope_type`` as newer files write it, and an ``attention_factor`` the
    file gives is the cos and sin's scale as given."""
    f = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if scaling is None:
        return f.astype(np.float32), 1.0, 1.0
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "yarn":
        raise NotImplementedError(f"rope_scaling {kind!r}")
    factor = float(scaling["factor"])
    low, high = yarn_bounds(dim, float(theta), scaling)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = f * (1.0 - ramp) + f / factor * ramp
    m_all = _yarn_mscale(factor, scaling.get("mscale_all_dim", 0.0))
    return (inv.astype(np.float32), scaling.get(
        "attention_factor",
        _yarn_mscale(factor, scaling.get("mscale", 1.0)) / m_all),
        m_all * m_all)


@dataclass
class DeepseekV2Config(HeldExpertsConfig):
    """Sizes under the names of the model's ``config.json``."""
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 60
    first_k_dense_replace: int = 1
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e4
    rope_scaling: Optional[dict] = field(default_factory=lambda: dict(YARN))
    n_routed_experts: int = 160
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 163840
    initializer_range: float = 0.02
    #: (first, count): the routed experts held here; None: all of them
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.n_routed_experts % self.n_group:
            raise ValueError(
                f"n_routed_experts {self.n_routed_experts} is not "
                f"n_group {self.n_group} groups of experts")
        if not 1 <= self.topk_group <= self.n_group:
            raise ValueError(f"topk_group {self.topk_group} of "
                             f"n_group {self.n_group}")

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope) ** -0.5 * m ** 2``: 0.11472 as published."""
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * yarn_table(self.qk_rope_head_dim, self.rope_theta,
                         self.rope_scaling)[2]

    def attention_params(self) -> int:
        h, nh = self.hidden_size, self.num_attention_heads
        return h * self.q_lora_rank + self.q_lora_rank \
            + self.q_lora_rank * nh * (self.qk_nope_head_dim
                                       + self.qk_rope_head_dim) \
            + h * (self.kv_lora_rank + self.qk_rope_head_dim) \
            + self.kv_lora_rank \
            + self.kv_lora_rank * nh * (self.qk_nope_head_dim
                                        + self.v_head_dim) \
            + nh * self.v_head_dim * h

    def layer_params(self, layer: int) -> int:
        """Parameters of one layer as held here (the held experts alone)."""
        h = self.hidden_size
        n = self.attention_params() + 2 * h
        if not self.is_moe(layer):
            return n + 3 * h * self.intermediate_size
        return n + h * self.n_routed_experts + 3 * h * (
            self.moe_intermediate_size * self.held[1] + self.shared_width)

    @staticmethod
    def deepseek_v2():
        """The catalog row: 60 layers, 160 experts, 102,400 words."""
        return DeepseekV2Config()

    @staticmethod
    def tiny(**kw):
        """Unit-test sizes: the leading dense layer and two expert layers,
        16 experts in 4 groups of which a token keeps 2, a YaRN block whose
        original length (16) a few dozen tokens pass, pages of 4."""
        base = dict(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=16, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=16, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            rope_theta=100.0,
            rope_scaling=dict(YARN, factor=8,
                              original_max_position_embeddings=16,
                              beta_fast=4),
            n_routed_experts=16, n_shared_experts=2, num_experts_per_tok=3,
            n_group=4, topk_group=2, routed_scaling_factor=4.0,
            max_position_embeddings=128, initializer_range=0.2)
        base.update(kw)
        return DeepseekV2Config(**base)


class DeepseekV2Attention(nn.Layer):
    """The weights of one layer's latent attention, under the names
    ``models/dots3.py`` gives the matrices the two models share."""

    def __init__(self, c: DeepseekV2Config):
        super().__init__()
        h, nh = c.hidden_size, c.num_attention_heads
        init, one = I.Normal(0.0, c.initializer_range), I.Constant(1.0)
        self.q_a = Weight([h, c.q_lora_rank], init)
        self.q_a_norm = Weight([c.q_lora_rank], one)
        self.q_b = Weight(
            [c.q_lora_rank,
             nh * (c.qk_nope_head_dim + c.qk_rope_head_dim)], init)
        self.kv_a = Weight([h, c.kv_lora_rank + c.qk_rope_head_dim], init)
        self.kv_a_norm = Weight([c.kv_lora_rank], one)
        self.kv_b = Weight(
            [c.kv_lora_rank, nh * (c.qk_nope_head_dim + c.v_head_dim)], init)
        self.o = Weight([nh * c.v_head_dim, h], init)


class DeepseekV2Block(nn.Layer):
    def __init__(self, c: DeepseekV2Config, layer: int):
        super().__init__()
        one = I.Constant(1.0)
        self.moe = c.is_moe(layer)
        self.ln_1 = Weight([c.hidden_size], one)
        self.attn = DeepseekV2Attention(c)
        self.ln_2 = Weight([c.hidden_size], one)
        if self.moe:
            self.ffn = HeldMoEMLP(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, c.held,
                initializer_range=c.initializer_range,
                out_initializer_range=c.initializer_range,
                scoring="softmax", shared_width=c.shared_width,
                n_group=c.n_group, topk_group=c.topk_group,
                routed_scaling=c.routed_scaling_factor)
        else:
            self.ffn = SwiGLUMLP(c)


class TickRecord(_tick.TickRecord):
    """Its ticks hand out no selected set and no window's log-sum."""
    STATS = TICK_STATS


class DeepseekV2(LayerwiseLM):
    """The served model: ``LayerwiseLM``'s weights, what caches it keeps and
    the tick's forward."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__(config, DeepseekV2Block)

    # -- what ServingEngine asks of a model (models/tick.py) -------------
    def cache_spec(self) -> dict:
        c = self.config
        return {"kind": "latent", "full_layers": c.num_hidden_layers,
                "latent_width": c.kv_lora_rank + c.qk_rope_head_dim,
                "tick_record": TickRecord}

    def ragged_apply(self, stacked, other, pools, tokens, tok_pos, tok_limit,
                     row_tab, row_pos0, row_len, sample_ix, *, decode_rows,
                     chunk_width, has_chunks=None):
        return deepseek_v2_ragged_apply(
            self.config, stacked, other, pools, tokens, tok_pos, tok_limit,
            row_tab, row_pos0, row_len, sample_ix, decode_rows, chunk_width,
            has_chunks=has_chunks)


# --------------------------------------------------------------------------
# the tick's forward
# --------------------------------------------------------------------------
def rope_by_table(x, pos, inv_freq, scale: float = 1.0):
    """``x`` [NT, heads, d] rotated by ``pos`` [NT] in the rotate-half
    convention, the angle of pair ``(j, j + d/2)`` ``pos * inv_freq[j]``;
    cos and sin times ``scale`` (YaRN's ``mscale`` ratio: 1 as published).
    A table of fewer than ``d / 2`` pairs turns the first ``2 len(inv_freq)``
    columns of a head and passes the others (a partial rotary factor)."""
    d = 2 * len(inv_freq)
    if d < x.shape[-1]:
        return jnp.concatenate([rope_by_table(x[..., :d], pos, inv_freq,
                                              scale), x[..., d:]], -1)
    ang = pos.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * scale
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return (xf * cos + half * sin).astype(x.dtype)


def _latent_queries(c: DeepseekV2Config, hn, p, pos, rope):
    """Over the flat tokens ``hn`` [NT, h]: the absorbed queries ``[NT, NH,
    C + R]`` (``q_nope`` carried into the latent space by ``W_kvb``'s key
    half, beside the rotated ``q_pe``) and the row to cache ``[NT, C + R]``,
    ``(c_kv, RoPE(k_pe))``."""
    nh, nope, rd = c.num_attention_heads, c.qk_nope_head_dim, \
        c.qk_rope_head_dim
    rank, eps = c.kv_lora_rank, c.rms_norm_eps
    inv, cs, _ = rope
    c_q = rms(hn @ p["attn.q_a.weight"], p["attn.q_a_norm.weight"], eps)
    q = (c_q @ p["attn.q_b.weight"]).reshape(-1, nh, nope + rd)
    q_pe = rope_by_table(q[..., nope:], pos, inv, cs)
    kv = hn @ p["attn.kv_a.weight"]
    c_kv = rms(kv[:, :rank], p["attn.kv_a_norm.weight"], eps)
    k_pe = rope_by_table(kv[:, None, rank:], pos, inv, cs)[:, 0]
    w_k = p["attn.kv_b.weight"].reshape(rank, nh,
                                        nope + c.v_head_dim)[..., :nope]
    q_lat = jnp.einsum("tnd,cnd->tnc", q[..., :nope], w_k)
    return (jnp.concatenate([q_lat, q_pe], -1),
            jnp.concatenate([c_kv, k_pe], -1))


def _attention_out(c: DeepseekV2Config, x, o_lat, p):
    """The values carried out of the latent space, projected and added to
    the residual stream."""
    w_v = p["attn.kv_b.weight"].reshape(
        c.kv_lora_rank, c.num_attention_heads,
        c.qk_nope_head_dim + c.v_head_dim)[..., c.qk_nope_head_dim:]
    o = jnp.einsum("tnc,cnd->tnd", o_lat.astype(x.dtype), w_v)
    return x + o.reshape(o.shape[0], -1) @ p["attn.o.weight"]


def deepseek_v2_ragged_apply(c: DeepseekV2Config, stacked, other, pools,
                             tokens, tok_pos, tok_limit, row_tab, row_pos0,
                             row_len, sample_ix, decode_rows: int,
                             chunk_width: int, has_chunks=None):
    """Mixed prefill/decode forward over latent pools: the arguments of
    ``models/dots3.dots3_ragged_apply`` (``row_tab`` its pair, of which the
    windowed layers' tables are not read). Decode rows and chunk rows attend
    in the absorbed form (``ops/latent_attention.latent_attention``, in the
    spelling the platform and the shapes pick where this is traced), under
    two scopes (``blk/attn/mla_decode``, ``blk/attn/mla_chunk``) so that a
    trace tells them apart.

    Returns ``(logits [S, V], pools, aux)``: ``aux["stats"]`` float32
    ``[len(TICK_STATS)]`` (the share of live tokens whose kept groups
    include the held experts' group or groups, the rows the held experts
    were given a layer, their fullest over their mean, the share of them
    with a row: means over the expert layers; then what one layer's
    attention saw, the visible query-key pairs and the rows' live keys of
    the decode rows' call and of the chunk rows'), ``aux["routed"]`` ``[expert
    layers, S, top_k]`` int32 the experts each sampled row chose,
    ``aux["top_logit"]`` ``[S]`` float32 the sampled rows' largest logit,
    and ``aux["selected"]``, ``aux["window_lse"]`` of no layers
    (``TickRecord`` reads them)."""
    del has_chunks
    tab, _ = row_tab
    nt, nd, w = tokens.shape[0], decode_rows, chunk_width
    ps, nps = pools.page_size, tab.shape[1]
    eps = c.rms_norm_eps
    rope = yarn_table(c.qk_rope_head_dim, c.rope_theta, c.rope_scaling)
    scale = c.softmax_scale
    first, count = c.held
    per_group = c.n_routed_experts // c.n_group
    with annotate("tick/embed"):
        x = other["embeddings.wte.weight"][tokens]              # [NT, h]
    rows_ = TickRows(ps, nps, tok_pos, tok_limit, row_pos0, nt, nd, w)
    page = rows_.page_of(tab)
    off = tok_pos % ps
    wrote = rows_.touched(page, tab)
    live = rows_.live(tab, row_len)
    n_live = jnp.maximum(jnp.sum(live), 1)
    # what a layer's attention sees: a live query at t sees t + 1 keys, a
    # live row holds pos0 + len latents
    pairs = jnp.where(live, tok_pos + 1, 0).astype(jnp.float32)
    keys = jnp.where((row_len > 0) & (tab[:, 0] > 0), jnp.minimum(
        row_pos0 + row_len, nps * ps), 0).astype(jnp.float32)
    attended = jnp.stack([jnp.sum(pairs[:nd]), jnp.sum(keys[:nd]),
                          jnp.sum(pairs[nd:]), jnp.sum(keys[nd:])])

    def attention(x, pl, p, layer):
        with annotate("blk/qkv"):
            hn = rms(x, p["ln_1.weight"], eps)
            q, row = _latent_queries(c, hn, p, tok_pos, rope)
        with annotate("blk/latent_scatter"):
            pl = pl.scatter_latent(layer, page, off, row, wrote)

        def attend(rows, cut):
            with annotate("blk/attn/mla_decode" if cut.t == 1
                          else "blk/attn/mla_chunk"):
                return cut.flat(pl.attend(
                    layer, cut(q), tab[rows], row_pos0[rows], row_len[rows],
                    c.kv_lora_rank, scale))

        o_lat = rows_.groups(attend)
        with annotate("blk/attn_out"):
            x = _attention_out(c, x, o_lat, p)
        return x, pl

    def ffn(x, p, moe: bool):
        with annotate("blk/ffn"):
            h2 = rms(x, p["ln_2.weight"], eps)
            if not moe:
                mid = jax.nn.silu(h2 @ p["ffn.fc_gate.weight"]) \
                    * (h2 @ p["ffn.fc_in.weight"])
                return x + mid @ p["ffn.fc_out.weight"], ()
            y, rows = held_moe(
                h2, p["ffn.gate"], p["ffn.w_gate"], p["ffn.w_up"],
                p["ffn.w_down"], c.num_experts_per_tok, c.held,
                scoring="softmax",
                shared=(p["ffn.shared_gate"], p["ffn.shared_up"],
                        p["ffn.shared_down"]),
                n_group=c.n_group, topk_group=c.topk_group,
                routed_scaling=c.routed_scaling_factor)
            rows = rows.astype(jnp.float32)
            with annotate("moe/route"):
                # what the tick says of its routing (held_moe's own rule
                # again, on scores [E, NT]: small beside the experts)
                score = jax.nn.softmax(jnp.dot(
                    p["ffn.gate"].astype(h2.dtype).T, h2.T,
                    preferred_element_type=jnp.float32), axis=0)
                kept = kept_groups(score, c.n_group, c.topk_group)
                mine = jnp.any(kept[first // per_group:
                                    -(-(first + count) // per_group)], 0)
                hit = jnp.sum(jnp.where(live, mine, False)) / n_live
                chosen = jax.lax.top_k(jnp.where(
                    jnp.repeat(kept[:, sample_ix], per_group, axis=0),
                    score[:, sample_ix], 0.0).T,
                    c.num_experts_per_tok)[1].astype(jnp.int32)
            return x + y.astype(x.dtype), ((jnp.stack([
                hit, jnp.sum(rows), jnp.max(rows) / jnp.maximum(
                    jnp.mean(rows), 1e-9), jnp.mean(rows > 0)]), chosen),)

    stats_moe = []
    for i in range(c.num_hidden_layers):
        p = stacked[f"layer{i}"]
        x, pools = attention(x, pools, p, i)
        x, f = ffn(x, p, c.is_moe(i))
        stats_moe.extend(f)
    with annotate("tick/head"):
        last = rms(x[sample_ix], other["ln_f.weight"], eps)
        logits = last @ other["lm_head.weight"]                 # [S, V]
        top = jnp.max(logits.astype(jnp.float32), -1)
    n_s = sample_ix.shape[0]
    per_moe = jnp.mean(jnp.stack([m for m, _ in stats_moe]), 0) \
        if stats_moe else jnp.zeros((4,), jnp.float32)
    routed = jnp.stack([r for _, r in stats_moe]) if stats_moe else \
        jnp.zeros((0, n_s, c.num_experts_per_tok), jnp.int32)
    aux = {"stats": jnp.concatenate([per_moe, attended]), "routed": routed,
           "top_logit": top,
           "selected": jnp.zeros((0, n_s, 0), bool),
           "window_lse": jnp.zeros((0, n_s), jnp.float32)}
    return logits, pools, aux
