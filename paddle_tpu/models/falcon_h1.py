"""Falcon-H1 (huggingface.co/tiiuae/Falcon-H1-34B-Instruct config.json,
``model_type`` ``falcon_h1``), served.

Every block runs **a Mamba-2 (SSD) mixer and grouped-query attention side by
side on one normed input and adds both** (no other served model keeps a state
*and* pages in one layer: Olmo-Hybrid and Ling alternate), then a SwiGLU; muP
multipliers scale nine points of the block::

    e      = Embed[ids] * embedding_multiplier
    n      = RMSNorm(x)
    u      = (n * ssm_in_multiplier) W_in * mup         # [z | x | B | C | dt]
    xBC    = SiLU(causal_conv4([x | B | C]) + conv_bias)
    dt     = softplus(dt + dt_bias) ;  A = -exp(A_log)
    S_t    = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T ;  y_t = S_t C_t + D x_t
    m      = GroupRMSNorm(y * SiLU(z)) W_out * ssm_out_multiplier
    q,k,v  = (n * attention_in_multiplier) W_qkv ;  k = k * key_multiplier
    a      = softmax(RoPE(q) RoPE(k)^T / sqrt(d), causal) v W_o * attention_out_multiplier
    h      = x + m + a
    g      = RMSNorm(h)
    y      = h + ((g W_up) * SiLU((g W_gate) * mlp_multipliers[0])) W_down * mlp_multipliers[1]
    logits = RMSNorm(y_last) W_head * lm_head_multiplier

It is served over ``serving.paged_cache.SSDStatePools``: a ``cache_spec()``
of kind ``"state"`` whose ``layers == state_layers == num_hidden_layers``
(**every layer has both** a float32 state ``[heads, N, P]`` a slot with the
convolution's last three positions, and K/V pages), ``"rule": "ssd"``, and
``key_value_heads`` fewer than ``heads``, which gives the pages the grouped
layout (``paged_cache.GroupedPools``: 4 heads of 128 unpadded, 2,048 B a
token a layer). The forward touches the caches through the pools' methods
alone: ``prep`` then ``step`` for the decode rows, ``prep`` then ``chunk``
for the chunk rows (a tenant's first chunk enters at zero, ``fresh``), and
``scatter`` then ``attend`` for both, in the same layer.

**The layers are alike and the tick unrolls them** on ``tick.LayerwiseLM``
rather than scanning one block as ``models/gpt.py`` does: the weights stay
each layer's own arrays as they are drawn (a scan wants them stacked: a
second copy of 7.7 GB while it is made, on a chip that holds 8.4 GB of
weights beside 5.1 GB of caches), a kernel's layer index is a constant, and
nine layers of six kernels compile in well under a minute.

``models/falcon_h1_reference.py`` is the plain float32 reference of the same
equations; it reads this model's weights by the names given here and none of
its code. There is no training forward.

What ``config.json`` does not settle, and how it is read here (the
configuration file's ``assumed``): ``ssm_multipliers`` scale the segments
``[z, x, B, C, dt]`` in that order; ``dt`` is not clipped after the softplus
(``time_step_limit`` (0, inf)); the gated norm is over groups of ``d_ssm /
n_groups`` channels with the gate applied before it
(``mamba_norm_before_gate`` false); heads ``0 .. H/G - 1`` read group 0;
``A_log``, ``dt_bias`` and ``D`` are drawn as Mamba-2's initialiser draws
them; RoPE turns all of a head's dimensions, paired half-split; q, k and v
are one matrix's columns (storage, not mathematics).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import initializer as I
from ..profiler.trace import annotate
from .gpt import rope_at
from .olmo_hybrid import TICK_STATS, TickRecord, _DtBias, _LogUniform
from .tick import LayerwiseLM, SwiGLUMLP, TickRows, Weight, rms

__all__ = ["FalconH1Config", "FalconH1", "falcon_h1_ragged_apply",
           "TickRecord", "TICK_STATS"]

_F32 = jnp.float32
#: the most queries of a chunk row one call of the attention takes as a row
#: (``falcon_h1_ragged_apply``'s ``attend``): the kernel keeps a row's
#: scores for a key/value head's every query head in VMEM (an operand of
#: ``5 x 64`` rows; a decode row's 5 queries are one tile of 16)
_ATTN_QUERIES = 64


@dataclass
class FalconH1Config:
    """Sizes and multipliers under the names of the model's ``config.json``."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_norm_before_gate: bool = False
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    #: over the projection's segments ``[z, x, B, C, dt]``
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    #: on the gate's projection, and on the SwiGLU's output
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369,
                                          0.011160714285714284)

    def __post_init__(self):
        # config.json writes 100000000000: an integer past int32, which a
        # traced power would refuse
        self.rope_theta = float(self.rope_theta)
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError(
                f"mamba_d_ssm {self.mamba_d_ssm} is not {self.mamba_n_heads} "
                f"heads of {self.mamba_d_head}")
        if self.mamba_n_heads % self.mamba_n_groups \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads that do not divide into their groups")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers is five numbers ([z, x, B, C, "
                             "dt]) and mlp_multipliers two")
        if self.mamba_norm_before_gate or not self.mamba_conv_bias:
            raise NotImplementedError(
                "the gated norm after the gate and a convolution with a "
                "bias are what this model serves")

    @property
    def max_seq_len(self) -> int:           # the engine's name for it
        return self.max_position_embeddings

    @property
    def conv_width(self) -> int:
        """``[x | B | C]``: what the short convolution runs over."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def proj_width(self) -> int:
        """``[z | x | B | C | dt]``."""
        return self.mamba_d_ssm + self.conv_width + self.mamba_n_heads

    @property
    def q_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    def layer_params(self, layer: int = 0) -> int:
        h = self.hidden_size
        ssd = h * self.proj_width + self.mamba_d_ssm * h \
            + (self.mamba_d_conv + 1) * self.conv_width \
            + 3 * self.mamba_n_heads + self.mamba_d_ssm
        attn = h * (self.q_width + 2 * self.kv_width) + self.q_width * h
        return ssd + attn + 3 * h * self.intermediate_size + 2 * h

    def num_params(self) -> int:
        return self.num_hidden_layers * self.layer_params() \
            + 2 * self.vocab_size * self.hidden_size + self.hidden_size

    @staticmethod
    def falcon_h1_34b():
        """The catalog row: 72 layers, 261,120 words."""
        return FalconH1Config()

    @staticmethod
    def tiny(**kw):
        """Unit-test sizes: 3 layers of hidden 64, 4 SSD heads of 8 x 16 in
        2 groups, 4 query heads over 2 key/value heads of 16."""
        base = dict(
            vocab_size=96, hidden_size=64, intermediate_size=96,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, mamba_d_ssm=32,
            mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
            mamba_n_groups=2, max_position_embeddings=256,
            rope_theta=10000.0, initializer_range=0.2,
            embedding_multiplier=1.5, lm_head_multiplier=0.5,
            ssm_in_multiplier=0.8, ssm_out_multiplier=1.25,
            ssm_multipliers=(1.1, 0.9, 0.7, 1.3, 0.6),
            attention_in_multiplier=0.9, attention_out_multiplier=1.2,
            key_multiplier=0.75, mlp_multipliers=(0.85, 1.15))
        base.update(kw)
        return FalconH1Config(**base)


class SSDMixer(nn.Layer):
    """The weights of a block's state-space mixer."""

    def __init__(self, c: FalconH1Config):
        super().__init__()
        h, heads, taps = c.hidden_size, c.mamba_n_heads, c.mamba_d_conv
        init, one = I.Normal(0.0, c.initializer_range), I.Constant(1.0)
        self.w_in = Weight([h, c.proj_width], init)   # [z | x | B | C | dt]
        self.conv = Weight([taps, c.conv_width],
                           I.Uniform(-taps ** -0.5, taps ** -0.5))
        self.conv_bias = Weight([c.conv_width],
                                I.Uniform(-taps ** -0.5, taps ** -0.5))
        self.A_log = Weight([heads], _LogUniform(1.0, 16.0))
        self.dt_bias = Weight([heads], _DtBias())
        self.D = Weight([heads], one)
        self.norm = Weight([c.mamba_d_ssm], one)
        self.w_out = Weight([c.mamba_d_ssm, h], init)


class GroupedAttention(nn.Layer):
    """The weights of a block's attention."""

    def __init__(self, c: FalconH1Config):
        super().__init__()
        init = I.Normal(0.0, c.initializer_range)
        self.qkv = Weight([c.hidden_size, c.q_width + 2 * c.kv_width], init)
        self.o = Weight([c.q_width, c.hidden_size], init)


class FalconH1Block(nn.Layer):
    def __init__(self, c: FalconH1Config, layer: int):
        super().__init__()
        del layer                       # the layers are alike
        one = I.Constant(1.0)
        self.ln_1 = Weight([c.hidden_size], one)        # before both mixers
        self.ssd = SSDMixer(c)
        self.attn = GroupedAttention(c)
        self.ln_2 = Weight([c.hidden_size], one)        # before the SwiGLU
        self.ffn = SwiGLUMLP(c)


class FalconH1(LayerwiseLM):
    """The served model: ``LayerwiseLM``'s weights, what caches it keeps and
    the tick's forward."""

    def __init__(self, config: FalconH1Config):
        super().__init__(config, FalconH1Block)

    # -- what ServingEngine asks of a model (models/tick.py) -------------
    def cache_spec(self) -> dict:
        c = self.config
        return {"kind": "state", "rule": "ssd",
                "layers": c.num_hidden_layers,
                "heads": c.num_attention_heads,
                "key_value_heads": c.num_key_value_heads,
                "head_dim": c.head_dim,
                "state_layers": c.num_hidden_layers,
                "state_heads": c.mamba_n_heads,
                # a head's state is P x N; it is kept as [N, P] (ops/ssd)
                "key_dim": c.mamba_d_state, "value_dim": c.mamba_d_head,
                "conv_width": c.conv_width, "conv_taps": c.mamba_d_conv,
                "conv_bias": c.mamba_conv_bias,
                "tick_record": TickRecord}

    def ragged_apply(self, stacked, other, pools, tokens, tok_pos, tok_limit,
                     row_tab, row_pos0, row_len, sample_ix, *, decode_rows,
                     chunk_width, has_chunks=None):
        return falcon_h1_ragged_apply(
            self.config, stacked, other, pools, tokens, tok_pos, tok_limit,
            row_tab, row_pos0, row_len, sample_ix, decode_rows, chunk_width,
            has_chunks=has_chunks)


# --------------------------------------------------------------------------
# the tick's forward
# --------------------------------------------------------------------------
def _mup(c: FalconH1Config):
    """``ssm_multipliers`` a column of the projection, float32 ``[z | x | B |
    C | dt]``."""
    bc = c.mamba_n_groups * c.mamba_d_state
    widths = (c.mamba_d_ssm, c.mamba_d_ssm, bc, bc, c.mamba_n_heads)
    return jnp.concatenate([jnp.full((w,), m, _F32)
                            for w, m in zip(widths, c.ssm_multipliers)])


def falcon_h1_ragged_apply(c: FalconH1Config, stacked, other, pools, tokens,
                           tok_pos, tok_limit, row_tab, row_pos0, row_len,
                           sample_ix, decode_rows: int, chunk_width: int,
                           has_chunks=None):
    """Mixed prefill/decode forward over ``SSDStatePools``: the arguments of
    ``models/gpt.gpt_ragged_apply``, ``row_tab`` the pair ``(page tables [R,
    NPs], state slots [R])`` that ``StatePagePool.row_tables`` gives.

    A decode row is **live** if it carries a state slot and its token has a
    page of its slot to be written to; the others (an empty slot, a slot
    between two chunks of its prompt, a slot whose chunk row rides in this
    tick) take the null slot. A chunk row at position 0 is a tenant's first:
    it enters at a zero state and a zero history.

    **``has_chunks``** (``models/tick.py``: false on a tick whose chunk row
    is a pad) goes to ``TickRows.dense``: what a layer does to its rows
    before the pools (``before``: ``ln_1``, the mixer's and the attention's
    input matrices, RoPE) and after them (``after``: the gated norm, both
    output matrices, the SwiGLU) is row-wise, so one layer's ``after`` and
    the next one's ``before`` are one stretch that such a tick runs over
    its decode rows alone (80 rows read a matrix as fast as the HBM gives
    it; 336 were bound by the products on 256 pads). ``mixer`` and
    ``attention``, the calls on the pools, stay outside every ``cond``.

    Returns ``(logits [S, V], pools, aux)`` with ``aux`` as
    ``models/olmo_hybrid.olmo_hybrid_ragged_apply`` gives it (``stats`` in
    ``TICK_STATS``' order, ``top_logit``)."""
    tab, slots = row_tab
    nt, nd, w = tokens.shape[0], decode_rows, chunk_width
    ps, nps = pools.page_size, tab.shape[1]
    eps = c.rms_norm_eps
    heads, kvh, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    sh, sp, sn, sg = (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                      c.mamba_n_groups)
    d_ssm, cw = c.mamba_d_ssm, c.conv_width
    with annotate("tick/embed"):
        x = other["embeddings.wte.weight"][tokens] * c.embedding_multiplier
    rows_ = TickRows(ps, nps, tok_pos, tok_limit, row_pos0, nt, nd, w,
                     has_chunks)
    page = rows_.page_of(tab)
    off = tok_pos % ps
    touched = rows_.touched(page, tab)
    nch = rows_.nch
    slots = jnp.asarray(slots, jnp.int32)
    # the decode rows that carry a tenant's next token
    dec_slots = jnp.where(page[:nd] > 0, slots[:nd], 0)
    ch_slots, ch_len = slots[nd:], row_len[nd:]
    fresh = row_pos0[nd:] == 0
    live_tok = rows_.live(tab, row_len)
    keys = jnp.where((row_len > 0) & (tab[:, 0] > 0), jnp.minimum(
        row_pos0 + row_len, nps * ps), 0).astype(_F32)
    pairs = jnp.where(live_tok, tok_pos + 1, 0).astype(_F32)
    stats = jnp.stack([
        jnp.sum(dec_slots > 0).astype(_F32), jnp.sum(ch_len).astype(_F32),
        jnp.sum(jnp.where(dec_slots > 0, keys[:nd], 0.0)),
        jnp.sum(keys[nd:]), jnp.sum(pairs[nd:])])
    mup = _mup(c)

    def split(a):
        """The activated ``[x | B | C]`` of rows ``[..., C]`` by head and
        group."""
        lead = a.shape[:-1]
        bc = sg * sn
        return (a[..., :d_ssm].reshape(lead + (sh, sp)),
                a[..., d_ssm:d_ssm + bc].reshape(lead + (sg, sn)),
                a[..., d_ssm + bc:].reshape(lead + (sg, sn)))

    def before(p, x, pos):
        """What a layer makes of its rows before it touches the pools: the
        normed input through the mixer's and the attention's matrices."""
        with annotate("blk/ssd/proj"):
            n = rms(x, p["ln_1.weight"], eps)
            u = (n * c.ssm_in_multiplier) @ p["ssd.w_in.weight"]
            u = (u.astype(_F32) * mup).astype(n.dtype)
            z, xbc = u[:, :d_ssm], u[:, d_ssm:d_ssm + cw]
            dt = jax.nn.softplus(u[:, d_ssm + cw:].astype(_F32)
                                 + p["ssd.dt_bias.weight"].astype(_F32))
        with annotate("blk/qkv"):
            qkv = (n * c.attention_in_multiplier) @ p["attn.qkv.weight"]
            qw, kw = c.q_width, c.kv_width
            q = qkv[:, :qw].reshape(-1, 1, heads, hd)
            k = (qkv[:, qw:qw + kw] * c.key_multiplier).reshape(
                -1, 1, kvh, hd)
            v = qkv[:, qw + kw:].reshape(-1, 1, kvh, hd)
            q = rope_at(q, pos, c.rope_theta)
            k = rope_at(k, pos, c.rope_theta)
        return z, xbc, dt, q, k, v

    def mixer(pl, p, layer, xbc, dt):
        """The state's rule over the projected rows -> ``y`` float32, the
        decode rows' ``[nd, heads, P]`` and the chunk rows' ``[nch w, heads,
        P]``."""
        with annotate("blk/ssd/proj"):
            a_neg = -jnp.exp(p["ssd.A_log.weight"].astype(_F32))
            skip = p["ssd.D.weight"].astype(_F32)
        taps, bias = p["ssd.conv.weight"], p["ssd.conv_bias.weight"]
        outs = []
        if nd:
            with annotate("blk/ssd/prep"):
                act, pl = pl.prep(layer, dec_slots, xbc[:nd], taps, bias)
                xs, bs, cs = split(act)
            with annotate("blk/ssd/step"):
                y, pl = pl.step(layer, dec_slots, xs, bs, cs, dt[:nd], a_neg,
                                skip)
            outs.append(y)
        if nch:
            cut = lambda a: a[nd:].reshape(                 # noqa: E731
                (nch, w) + a.shape[1:])
            with annotate("blk/ssd/prep"):
                act, pl = pl.prep(layer, ch_slots, cut(xbc), taps, bias,
                                  fresh=fresh, row_len=ch_len)
                xs, bs, cs = split(act)
            with annotate("blk/ssd/chunk"):
                y, pl = pl.chunk(layer, ch_slots, fresh, ch_len, xs, bs, cs,
                                 cut(dt), a_neg, skip)
            outs.append(y.reshape(nch * w, sh, sp))
        return outs, pl

    def attention(pl, layer, q, k, v):
        with annotate("blk/kv_scatter"):
            pl = pl.scatter(layer, page, off, k, v, touched)

        def attend(rows, cut):
            # a chunk row attends in pieces of ``_ATTN_QUERIES`` (a later
            # piece sees the earlier ones' keys: all are written)
            n_, t = cut.n, cut.t
            pieces = t // _ATTN_QUERIES if t % _ATTN_QUERIES == 0 else 1
            first = jnp.tile(jnp.arange(pieces, dtype=jnp.int32)
                             * (t // pieces), n_)
            rep = lambda a: jnp.repeat(a[rows], pieces, axis=0)  # noqa: E731
            with annotate("blk/attn/full"):
                o = pl.attend(
                    layer, cut(q[:, 0]).reshape(
                        n_ * pieces, t // pieces, heads, hd),
                    rep(tab), rep(row_pos0) + first,
                    jnp.clip(rep(row_len) - first, 0, t // pieces))
                return cut.flat(o.reshape(n_, t, heads, hd))

        return rows_.groups(attend, join=False), pl

    def after(p, x, y, z, o):
        """What a layer makes of its rows once the pools have answered: the
        mixer's gated norm and both output matrices, then the SwiGLU."""
        with annotate("blk/ssd/out"):
            y = y.reshape(-1, d_ssm) * jax.nn.silu(z.astype(_F32))  # f32
            grp = y.reshape(-1, sg, d_ssm // sg)
            grp = grp * jax.lax.rsqrt(
                jnp.mean(jnp.square(grp), axis=-1, keepdims=True) + eps)
            y = grp.reshape(-1, d_ssm) * p["ssd.norm.weight"].astype(_F32)
            m = (y.astype(x.dtype) @ p["ssd.w_out.weight"]) \
                * c.ssm_out_multiplier
        with annotate("blk/attn_out"):
            a = (o.reshape(o.shape[0], -1).astype(x.dtype)
                 @ p["attn.o.weight"]) * c.attention_out_multiplier
        with annotate("blk/ffn"):
            x = x + m + a
            g = rms(x, p["ln_2.weight"], eps)
            mid = (g @ p["ffn.fc_in.weight"]) * jax.nn.silu(
                (g @ p["ffn.fc_gate.weight"]) * c.mlp_multipliers[0])
            return x + (mid @ p["ffn.fc_out.weight"]) * c.mlp_multipliers[1]

    def between(p, nxt, x, y, z, o, pos):
        x = after(p, x, y, z, o)
        return x, before(nxt, x, pos)

    # a layer's rows meet the pools in the middle of it, so the dense
    # stretch between two layers' pool calls is one layer's end and the
    # next one's start: one ``rows_.dense`` each (its own scope names the
    # branch itself, whose turnaround is the dense part's)
    layers = [stacked[f"layer{i}"] for i in range(c.num_hidden_layers)]
    pos = tok_pos[:, None]
    with annotate("blk/ssd/proj"):
        z, xbc, dt, q, k, v = rows_.dense(partial(before, layers[0]), x, pos)
    for i, p in enumerate(layers):
        y, pools = mixer(pools, p, i, xbc, dt)
        o, pools = attention(pools, i, q, k, v)
        with annotate("blk/ffn"):
            if i + 1 < len(layers):
                x, (z, xbc, dt, q, k, v) = rows_.dense(
                    partial(between, p, layers[i + 1]), x, y, z, o, pos)
            else:
                x = rows_.dense(partial(after, p), x, y, z, o)
    with annotate("tick/head"):
        last = rms(x[sample_ix], other["ln_f.weight"], eps)
        logits = (last @ other["lm_head.weight"]) * c.lm_head_multiplier
        top = jnp.max(logits.astype(_F32), -1)
    return logits, pools, {"stats": stats, "top_logit": top}
