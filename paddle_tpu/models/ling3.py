"""Ling-3.0-flash (huggingface.co/inclusionAI/Ling-3.0-flash config.json,
``model_type`` ``bailing_hybrid``), served.

Layers of two kinds in periods of ``layer_group_size`` 6: layer ``i`` is
latent attention (MLA) where ``(i + 1) % 6 == 0`` and Kimi Delta Attention
(KDA, arXiv:2510.26692) otherwise; the leading ``first_k_dense_replace``
layers carry a dense SwiGLU, the others a sigmoid-routed mixture limited to
``topk_group`` of ``n_group`` groups of experts (``noaux_tc``) with one
shared expert, of which this model holds a share (``distributed/moe.
held_moe``). With ``N(.)`` an RMSNorm of its own weight, a block is::

    x <- x + mixer(N1(x)) ; x <- x + FFN(N2(x))

- **KDA layer**: 32 heads of 128 x 128. ``[q|k|v] = SiLU(conv4(h W_qkv))``,
  q and k l2-normalised a head; a decay **a key channel**, full rank and
  bounded, ``g = kda_lower_bound * sigmoid(exp(A_log) (h W_f + dt_bias))``
  in (-5, 0); ``beta = sigmoid(h W_b)`` a head; the state ``S_t = (I -
  beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t =
  S_t^T q_t / sqrt(128)``; the output ``N_head(o) * sigmoid(h W_g)``
  through ``W_o``. No rotary.
- **MLA layer**: DeepSeek-V2-Lite's form (no query rank: one ``[h, 32 x
  192]`` product; ``kv_lora_rank`` 512 + 64 rotary dims cached a token;
  ``kv_a_layernorm``), RoPE at theta 6e6 on the 64 dims, softmax scale
  ``192^-0.5``, and a **head-wise output gate** ``sigmoid(h W_gate)[32]``
  on each head's output before ``W_o``, as ``models/dots3.py`` has it.
- **Router**: ``s = sigmoid(h W_r)`` over all ``num_experts``; a group's
  score is the sum of its two largest ``s + expert_bias``; the
  ``topk_group`` best groups stay; the ``num_experts_per_tok`` largest ``s
  + expert_bias`` within them are chosen, weighted by their ``s``
  renormalised over the chosen, times ``routed_scaling_factor``; the shared
  expert is added unweighted.

It is served layer by layer (``models/tick.py``: the protocol
``ServingEngine`` asks of a model, ``LayerwiseLM``, ``TickRows``) over
``serving.paged_cache.StatePools`` **with latent pages**: one float32 state
and the convolution's last three positions a slot for the KDA layers, beside
latent rows ``(c_kv, RoPE(k_pe))`` a token for the MLA layers, in one pool.
The forward touches them through the pools' methods alone: ``prep``,
``step`` and ``chunk`` (``ops/gdn.py``, the forms that take a decay a
channel) and ``scatter_latent`` and ``attend_latent`` (``ops/
latent_attention.latent_attention``, DeepSeek-V2's dense path).
``models/ling3_reference.py`` is the plain float32 reference of the same
equations; it reads this model's weights by the names given here and none
of its code. There is no training forward, and the multi-token-prediction
layer of the published model is no part of this file.

What ``config.json`` does not settle, and how it is read here (the
configuration file's ``assumed``): pre-norm blocks; no rotary in the KDA
layers and the delta rule's scale ``dk^-0.5`` (Kimi Linear's); ``A_log`` a
head and ``dt_bias`` a channel, drawn as fla's initialiser draws them; no
convolution bias; the rope columns taken as already in halves (HF permutes
interleaved pairs to halves: with seeded weights storage, not mathematics);
the output gates read the normed layer input; ``use_qk_norm`` is the
l2norm of q and k in the KDA layers and ``kv_a_layernorm`` in the MLA
layers; a SwiGLU limit of 0 is no clamp (the cut keeps no layer with
another).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..distributed.moe import HeldMoEMLP, held_moe, kept_groups
from ..nn import initializer as I
from ..profiler.trace import annotate
from .deepseek_v2 import rope_by_table, yarn_table
from .olmo_hybrid import _DtBias, _LogUniform
from .tick import (HeldExpertsConfig, LayerwiseLM, SwiGLUMLP, TickRows,
                   Weight, count_stats, rms)

_F32 = jnp.float32
KDA, MLA = "kda", "mla"

#: what one tick reports beside its tokens, in this order (``aux["stats"]``)
TICK_STATS = ("live_state_rows", "chunk_tokens", "decode_keys", "chunk_keys",
              "decode_pairs", "chunk_pairs", "group_hit_share", "expert_rows",
              "expert_load_max_over_mean", "experts_touched_share",
              "held_rows_unaccounted")


@dataclass
class Ling3Config(HeldExpertsConfig):
    """Sizes under the names of the model's ``config.json``."""
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    layer_group_size: int = 6
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    num_experts: int = 512
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    #: the deviation ``expert_bias`` is drawn at: a balancing rule moves it
    #: in the lineage; seeded, it is small against the scores' own spread
    select_bias_range: float = 0.0
    #: the published indices of the layers held, in order; None: all
    #: ``num_hidden_layers`` of them. A layer's kind and FFN follow from its
    #: published index
    layer_ids: Optional[Tuple[int, ...]] = None
    #: (first, count): the routed experts held here; None: all of them
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        self.layer_ids = tuple(range(self.num_hidden_layers)) \
            if self.layer_ids is None else tuple(self.layer_ids)
        if len(self.layer_ids) != self.num_hidden_layers:
            raise ValueError(
                f"layer_ids names {len(self.layer_ids)} layers, "
                f"num_hidden_layers {self.num_hidden_layers}")
        if self.num_experts % self.n_group:
            raise ValueError(f"num_experts {self.num_experts} is not "
                             f"n_group {self.n_group} groups of experts")
        if not 1 <= self.topk_group <= self.n_group:
            raise ValueError(f"topk_group {self.topk_group} of "
                             f"n_group {self.n_group}")
        if self.num_shared_experts != 1:
            raise ValueError("one shared expert is what is implemented")

    @property
    def n_routed_experts(self) -> int:      # ``HeldExpertsConfig``'s name
        return self.num_experts

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(MLA if (i + 1) % self.layer_group_size == 0 else KDA
                     for i in self.layer_ids)

    def is_moe(self, layer: int) -> bool:
        return self.layer_ids[layer] >= self.first_k_dense_replace

    @property
    def key_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def conv_width(self) -> int:
        return 3 * self.key_width

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def mixer_params(self, kind: str) -> int:
        h, nh, kw = self.hidden_size, self.num_attention_heads, \
            self.key_width
        if kind == KDA:
            return h * self.conv_width + 3 * h * kw + h * nh \
                + self.short_conv_kernel_size * self.conv_width + nh + kw \
                + self.head_dim
        return h * nh * (self.qk_nope_head_dim + self.qk_rope_head_dim) \
            + h * (self.kv_lora_rank + self.qk_rope_head_dim) \
            + self.kv_lora_rank + self.kv_lora_rank * nh * (
                self.qk_nope_head_dim + self.v_head_dim) + h * nh \
            + nh * self.v_head_dim * h

    def layer_params(self, layer: int) -> int:
        """Parameters of one layer as held here (the held experts alone)."""
        h = self.hidden_size
        n = self.mixer_params(self.layer_kinds[layer]) + 2 * h
        if not self.is_moe(layer):
            return n + 3 * h * self.intermediate_size
        return n + h * self.num_experts + self.num_experts + 3 * h * (
            self.moe_intermediate_size * self.held[1]
            + self.moe_shared_expert_intermediate_size)

    @staticmethod
    def ling3_flash():
        """The catalog row: 42 layers, 512 experts, 157,184 words."""
        return Ling3Config()

    @staticmethod
    def tiny(**kw):
        """Unit-test sizes: one leading dense layer and a period of (two
        KDA, one MLA) twice, 4 heads of 16 x 16 beside 4 latent heads, 16
        experts in 4 groups of which a token keeps 2."""
        base = dict(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
            num_hidden_layers=7, layer_group_size=3, first_k_dense_replace=1,
            num_attention_heads=4, head_dim=16, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            rope_theta=100.0, num_experts=16, num_experts_per_tok=3,
            n_group=4, topk_group=2, routed_scaling_factor=2.5,
            select_bias_range=0.02, max_position_embeddings=128,
            initializer_range=0.2)
        base.update(kw)
        return Ling3Config(**base)


class KimiDeltaAttention(nn.Layer):
    """The weights of a KDA layer's mixer."""

    def __init__(self, c: Ling3Config):
        super().__init__()
        h, nh, kw = c.hidden_size, c.num_attention_heads, c.key_width
        init, one = I.Normal(0.0, c.initializer_range), I.Constant(1.0)
        taps = c.short_conv_kernel_size
        self.qkv = Weight([h, c.conv_width], init)      # [q | k | v]
        self.conv = Weight([taps, c.conv_width],
                           I.Uniform(-taps ** -0.5, taps ** -0.5))
        self.f = Weight([h, kw], init)                  # the decay, full rank
        self.A_log = Weight([nh], _LogUniform(1.0, 16.0))
        self.dt_bias = Weight([kw], _DtBias())
        self.b = Weight([h, nh], init)                  # beta
        self.gate = Weight([h, kw], init)               # the output gate
        self.o_norm = Weight([c.head_dim], one)
        self.o = Weight([kw, h], init)


class LatentAttention(nn.Layer):
    """The weights of an MLA layer's mixer, under the names ``models/
    dots3.py`` gives the matrices the latent models share."""

    def __init__(self, c: Ling3Config):
        super().__init__()
        h, nh = c.hidden_size, c.num_attention_heads
        init, one = I.Normal(0.0, c.initializer_range), I.Constant(1.0)
        self.q = Weight(
            [h, nh * (c.qk_nope_head_dim + c.qk_rope_head_dim)], init)
        self.kv_a = Weight([h, c.kv_lora_rank + c.qk_rope_head_dim], init)
        self.kv_a_norm = Weight([c.kv_lora_rank], one)
        self.kv_b = Weight(
            [c.kv_lora_rank, nh * (c.qk_nope_head_dim + c.v_head_dim)], init)
        self.gate = Weight([h, nh], init)               # a head's gate
        self.o = Weight([nh * c.v_head_dim, h], init)


class Ling3Block(nn.Layer):
    def __init__(self, c: Ling3Config, layer: int):
        super().__init__()
        one = I.Constant(1.0)
        self.ln_1 = Weight([c.hidden_size], one)
        if c.layer_kinds[layer] == MLA:
            self.attn = LatentAttention(c)
        else:
            self.mix = KimiDeltaAttention(c)
        self.ln_2 = Weight([c.hidden_size], one)
        if c.is_moe(layer):
            self.ffn = HeldMoEMLP(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_per_tok, c.held,
                initializer_range=c.initializer_range,
                out_initializer_range=c.initializer_range,
                scoring="sigmoid", select_bias_range=c.select_bias_range,
                shared_width=c.moe_shared_expert_intermediate_size,
                n_group=c.n_group, topk_group=c.topk_group,
                routed_scaling=c.routed_scaling_factor)
        else:
            self.ffn = SwiGLUMLP(c)


class TickRecord:
    """This model's ticks (``aux`` of ``ling3_ragged_apply``): every drained
    tick's ``stats`` in the registry (``models/tick.count_stats``), for the
    requests a caller watches the largest logit, the experts chosen and the
    MLA layers' output of each row that chose a token, and for every request **where its latest
    token's row stood** (``models/olmo_hybrid.TickRecord``'s reading: the
    slot, and the cache position of its query, after which the slot's states
    hold that position's token and all before it)."""

    STATS = TICK_STATS

    def __init__(self):
        self.watch = lambda rid: True
        self._by_rid: dict = {}
        self._at: dict = {}

    def tick(self, aux: dict, positions, rids):
        count_stats(self.STATS, aux["stats"])
        watched = any(self.watch(rid) for rid in rids)
        tops = np.asarray(aux["top_logit"]) if watched else None
        routed = np.asarray(aux["routed"]) if watched else None
        said = np.asarray(aux["mla_out"]) if watched else None

        def note(rid: int, row: int) -> None:
            self._at[rid] = (row, int(positions[row]))
            if watched and self.watch(rid):
                rec = self._by_rid.setdefault(
                    rid, {"top": [], "routed": [], "mla": []})
                rec["top"].append(float(tops[row]))
                rec["routed"].append(routed[:, row])
                rec["mla"].append(said[:, row])

        return note

    def forget(self, keep) -> None:
        self._by_rid = {r: v for r, v in self._by_rid.items() if r in keep}
        self._at = {r: v for r, v in self._at.items() if r in keep}

    def has(self, rid: int) -> bool:
        return rid in self._by_rid

    def top_logits(self, rid: int) -> Tuple[float, ...]:
        return tuple(self._by_rid[rid]["top"])

    def routed_experts(self, rid: int):
        """``[tokens, expert layers, top_k]`` int32: the experts the row
        that chose each of request ``rid``'s tokens was routed to."""
        return np.stack(self._by_rid[rid]["routed"])

    def mla_outputs(self, rid: int):
        """``[tokens, MLA layers, heads]`` float32: ``aux["mla_out"]`` of the
        row that chose each of request ``rid``'s tokens."""
        return np.stack(self._by_rid[rid]["mla"])

    def stood_at(self, rid: int):
        """``(slot, position)`` of the row that chose request ``rid``'s
        latest token, watched or not; None before its first."""
        return self._at.get(rid)


class Ling3(LayerwiseLM):
    """The served model: ``LayerwiseLM``'s weights, what caches it keeps and
    the tick's forward."""

    def __init__(self, config: Ling3Config):
        super().__init__(config, Ling3Block)

    # -- what ServingEngine asks of a model (models/tick.py) -------------
    def cache_spec(self) -> dict:
        c = self.config
        latent = c.layer_kinds.count(MLA)
        return {"kind": "state", "layers": latent,
                "latent_width": c.kv_lora_rank + c.qk_rope_head_dim,
                "state_layers": c.num_hidden_layers - latent,
                "state_heads": c.num_attention_heads,
                "key_dim": c.head_dim, "value_dim": c.head_dim,
                "conv_width": c.conv_width,
                "conv_taps": c.short_conv_kernel_size,
                "tick_record": TickRecord}

    def ragged_apply(self, stacked, other, pools, tokens, tok_pos, tok_limit,
                     row_tab, row_pos0, row_len, sample_ix, *, decode_rows,
                     chunk_width, has_chunks=None):
        return ling3_ragged_apply(
            self.config, stacked, other, pools, tokens, tok_pos, tok_limit,
            row_tab, row_pos0, row_len, sample_ix, decode_rows, chunk_width,
            has_chunks=has_chunks)


# --------------------------------------------------------------------------
# the tick's forward
# --------------------------------------------------------------------------
def kda_gates(c: Ling3Config, f, b, p):
    """``(g [.., H, dk], beta [.., H])`` float32 from the decay's and
    ``beta``'s projections: the bounded gate (``kda_safe_gate``)."""
    nh, dk = c.num_attention_heads, c.head_dim
    f = f.astype(_F32).reshape(f.shape[:-1] + (nh, dk)) \
        + p["mix.dt_bias.weight"].astype(_F32).reshape(nh, dk)
    g = c.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(p["mix.A_log.weight"].astype(_F32))[:, None] * f)
    return g, jax.nn.sigmoid(b.astype(_F32))


def ling3_ragged_apply(c: Ling3Config, stacked, other, pools, tokens,
                       tok_pos, tok_limit, row_tab, row_pos0, row_len,
                       sample_ix, decode_rows: int, chunk_width: int,
                       has_chunks=None):
    """Mixed prefill/decode forward over ``StatePools`` with latent pages:
    the arguments of ``models/gpt.gpt_ragged_apply``, ``row_tab`` the pair
    ``(page tables [R, NPs], state slots [R])`` that ``StatePagePool.
    row_tables`` gives. Live and dead decode rows, a tenant's first chunk
    and the null slot are ``models/olmo_hybrid.olmo_hybrid_ragged_apply``'s.

    Returns ``(logits [S, V], pools, aux)``: ``aux["stats"]`` float32
    ``[len(TICK_STATS)]`` (the live decode rows, the chunk rows' tokens, the
    keys and the visible query-key pairs of the decode rows' and of the
    chunk rows' latent attention a layer; then, means over the expert
    layers, the share of live tokens whose kept groups include a held one,
    the rows the live tokens gave the held experts, their fullest over their
    mean, the share of them with a row, and how far the rows ``held_moe`` gave out lie
    from the tokens' choices of held experts as ``aux["routed"]``'s rule
    makes them: 0 where the two routings are one), ``aux["top_logit"]`` ``[S]`` float32 the
    sampled rows' largest logit, ``aux["routed"]`` ``[expert layers, S,
    top_k]`` int32 the experts each sampled row chose and ``aux["mla_out"]``
    ``[MLA layers, S, heads]`` float32 what is each sampled row's head's own
    of its latent output (the heads' mean taken out), under an alternating
    sign over the latent's channels, times the head's gate."""
    del has_chunks
    tab, slots = row_tab
    nt, nd, w = tokens.shape[0], decode_rows, chunk_width
    ps, nps = pools.page_size, tab.shape[1]
    eps, nh, dk = c.rms_norm_eps, c.num_attention_heads, c.head_dim
    nope, rank = c.qk_nope_head_dim, c.kv_lora_rank
    inv_freq = yarn_table(c.qk_rope_head_dim, c.rope_theta, None)[0]
    first, count = c.held
    per_group = c.num_experts // c.n_group
    with annotate("tick/embed"):
        x = other["embeddings.wte.weight"][tokens]              # [NT, h]
    rows_ = TickRows(ps, nps, tok_pos, tok_limit, row_pos0, nt, nd, w)
    page = rows_.page_of(tab)
    off = tok_pos % ps
    wrote = rows_.touched(page, tab)
    nch = rows_.nch
    slots = jnp.asarray(slots, jnp.int32)
    # the decode rows that carry a tenant's next token
    dec_slots = jnp.where(page[:nd] > 0, slots[:nd], 0)
    ch_slots, ch_len = slots[nd:], row_len[nd:]
    fresh = row_pos0[nd:] == 0
    live = rows_.live(tab, row_len)
    n_live = jnp.maximum(jnp.sum(live), 1)
    keys = jnp.where((row_len > 0) & (tab[:, 0] > 0), jnp.minimum(
        row_pos0 + row_len, nps * ps), 0).astype(_F32)
    pairs = jnp.where(live, tok_pos + 1, 0).astype(_F32)
    stats = [jnp.sum(dec_slots > 0).astype(_F32),
             jnp.sum(ch_len).astype(_F32),
             jnp.sum(jnp.where(dec_slots > 0, keys[:nd], 0.0)),
             jnp.sum(keys[nd:]),
             jnp.sum(jnp.where(dec_slots > 0, pairs[:nd], 0.0)),
             jnp.sum(pairs[nd:])]

    def kda(x, pl, p, layer):
        with annotate("blk/kda/proj"):
            hn = rms(x, p["ln_1.weight"], eps)
            qkv = hn @ p["mix.qkv.weight"]
            gate = hn @ p["mix.gate.weight"]
            g, beta = kda_gates(c, hn @ p["mix.f.weight"],
                                hn @ p["mix.b.weight"], p)
        taps = p["mix.conv.weight"]
        prep = lambda rows, slots, **kw: pl.prep(           # noqa: E731
            layer, slots, rows, taps, nh, dk, **kw)
        outs = []
        if nd:
            with annotate("blk/kda/prep"):
                q, k, v, pl = prep(qkv[:nd], dec_slots)
            with annotate("blk/kda/step"):
                o, pl = pl.step(layer, dec_slots, q, k, v, g[:nd], beta[:nd])
            outs.append(o)
        if nch:
            cut = lambda a: a[nd:].reshape(                 # noqa: E731
                (nch, w) + a.shape[1:])
            with annotate("blk/kda/prep"):
                q, k, v, pl = prep(cut(qkv), ch_slots, fresh=fresh,
                                   row_len=ch_len)
            with annotate("blk/kda/chunk"):
                o, pl = pl.chunk(layer, ch_slots, fresh, ch_len, q, k, v,
                                 cut(g), cut(beta))
            outs.append(o.reshape(nch * w, nh, dk))
        with annotate("blk/kda/out"):
            o = jnp.concatenate(outs, 0)                    # [NT, H, dv] f32
            ms = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
            y = o * jax.lax.rsqrt(ms + eps) \
                * p["mix.o_norm.weight"].astype(_F32)
            y = y.reshape(nt, -1) * jax.nn.sigmoid(gate.astype(_F32))
            x = x + y.astype(x.dtype) @ p["mix.o.weight"]
        return x, pl

    def mla(x, pl, p, layer):
        with annotate("blk/qkv"):
            hn = rms(x, p["ln_1.weight"], eps)
            q = (hn @ p["attn.q.weight"]).reshape(
                nt, nh, nope + c.qk_rope_head_dim)
            kv = hn @ p["attn.kv_a.weight"]
            c_kv = rms(kv[:, :rank], p["attn.kv_a_norm.weight"], eps)
            k_pe = rope_by_table(kv[:, None, rank:], tok_pos, inv_freq)[:, 0]
            w_kv = p["attn.kv_b.weight"].reshape(rank, nh,
                                                 nope + c.v_head_dim)
            q = jnp.concatenate([
                jnp.einsum("tnd,cnd->tnc", q[..., :nope], w_kv[..., :nope]),
                rope_by_table(q[..., nope:], tok_pos, inv_freq)], -1)
            gate = jax.nn.sigmoid((hn @ p["attn.gate.weight"]).astype(_F32))
        with annotate("blk/latent_scatter"):
            pl = pl.scatter_latent(layer, page, off,
                                   jnp.concatenate([c_kv, k_pe], -1), wrote)

        def attend(rows, cut):
            with annotate("blk/mla/decode" if cut.t == 1
                          else "blk/mla/chunk"):
                return cut.flat(pl.attend_latent(
                    layer, cut(q), tab[rows], row_pos0[rows], row_len[rows],
                    rank, c.softmax_scale))

        o_lat = rows_.groups(attend)
        with annotate("blk/attn_out"):
            o = jnp.einsum("tnc,cnd->tnd", o_lat.astype(x.dtype),
                           w_kv[..., nope:])
            o = o * gate[..., None].astype(o.dtype)
            x = x + o.reshape(nt, -1) @ p["attn.o.weight"]
            # what the sampled rows' heads attended to, for the check: the
            # layer's output is small beside the residual stream and most
            # of it is the plain mean of the values, which every head
            # shares in the latent space; what is left of a head's latent
            # output without the heads' mean is its own weighting of the
            # keys (the rotation's work), and the gate scales it
            lat = o_lat[sample_ix].astype(_F32)                 # [S, NH, C]
            sign = 1.0 - 2.0 * (jnp.arange(rank) % 2).astype(_F32)
            said = gate[sample_ix] * jnp.sum(
                (lat - jnp.mean(lat, 1, keepdims=True)) * sign, -1)
        return x, pl, said

    def ffn(x, p, moe: bool):
        with annotate("blk/ffn"):
            h2 = rms(x, p["ln_2.weight"], eps)
            if not moe:
                mid = jax.nn.silu(h2 @ p["ffn.fc_gate.weight"]) \
                    * (h2 @ p["ffn.fc_in.weight"])
                return x + mid @ p["ffn.fc_out.weight"], ()
            bias = p["ffn.select_bias"]
            y, rows = held_moe(
                h2, p["ffn.gate"], p["ffn.w_gate"], p["ffn.w_up"],
                p["ffn.w_down"], c.num_experts_per_tok, c.held,
                scoring="sigmoid", select_bias=bias,
                shared=(p["ffn.shared_gate"], p["ffn.shared_up"],
                        p["ffn.shared_down"]),
                n_group=c.n_group, topk_group=c.topk_group,
                routed_scaling=c.routed_scaling_factor)
            rows = rows.astype(_F32)
            with annotate("moe/route"):
                # what the tick says of its routing (held_moe's own rule
                # again, on scores [E, NT]: small beside the experts)
                biased = jax.nn.sigmoid(jnp.dot(
                    p["ffn.gate"].astype(h2.dtype).T, h2.T,
                    preferred_element_type=_F32)) \
                    + bias.astype(_F32)[:, None]
                kept = kept_groups(biased, c.n_group, c.topk_group, best=2)
                mine = jnp.any(kept[first // per_group:
                                    -(-(first + count) // per_group)], 0)
                hit = jnp.sum(jnp.where(live, mine, False)) / n_live
                chosen = jax.lax.top_k(jnp.where(
                    jnp.repeat(kept, per_group, axis=0), biased, -jnp.inf).T,
                    c.num_experts_per_tok)[1].astype(jnp.int32)  # [NT, K]
                # (held_moe routes every token of the buffer, the pad
                # tokens of a tick without a chunk too: they ride on the
                # null page and nothing reads them. What the tick says of
                # its experts is of the live tokens' rows)
                held_ = (chosen >= first) & (chosen < first + count)
                off = jnp.abs(jnp.sum(rows) - jnp.sum(held_))
                mine_rows = jnp.sum(
                    (chosen[:, :, None] - first == jnp.arange(
                        count, dtype=jnp.int32)) & live[:, None, None],
                    (0, 1)).astype(_F32)                        # [count]
            return x + y.astype(x.dtype), ((jnp.stack([
                hit, jnp.sum(mine_rows), jnp.max(mine_rows) / jnp.maximum(
                    jnp.mean(mine_rows), 1e-9), jnp.mean(mine_rows > 0),
                off.astype(_F32)]), chosen[sample_ix]),)

    stats_moe, said_mla, n_mla, n_kda = [], [], 0, 0
    for i, kind in enumerate(c.layer_kinds):
        p = stacked[f"layer{i}"]
        if kind == MLA:
            x, pools, said = mla(x, pools, p, n_mla)
            said_mla.append(said)
            n_mla += 1
        else:
            x, pools = kda(x, pools, p, n_kda)
            n_kda += 1
        x, f = ffn(x, p, c.is_moe(i))
        stats_moe.extend(f)
    with annotate("tick/head"):
        last = rms(x[sample_ix], other["ln_f.weight"], eps)
        logits = last @ other["lm_head.weight"]                 # [S, V]
        top = jnp.max(logits.astype(_F32), -1)
    n_s = sample_ix.shape[0]
    per_moe = jnp.mean(jnp.stack([m for m, _ in stats_moe]), 0) \
        if stats_moe else jnp.zeros((5,), _F32)
    routed = jnp.stack([r for _, r in stats_moe]) if stats_moe else \
        jnp.zeros((0, n_s, c.num_experts_per_tok), jnp.int32)
    return logits, pools, {
        "stats": jnp.concatenate([jnp.stack(stats), per_moe]),
        "top_logit": top, "routed": routed,
        "mla_out": jnp.stack(said_mla) if said_mla
        else jnp.zeros((0, n_s, nh), _F32)}
