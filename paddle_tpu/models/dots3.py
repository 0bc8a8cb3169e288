"""The language model of dots3-note-prev (huggingface.co/dots-studio/
dots3-note-prev config.json, ``model_type`` ``dots3_note``), served.

Two kinds of attention and two kinds of FFN in one stack:

- **full layers**: latent attention (MLA, arXiv:2405.04434 section 2.1)
  whose keys are a *selection*: a learned indexer (DeepSeek-V3.2-Exp's
  lightning indexer) scores every earlier position and each query attends
  the ``index_topk`` best. The cache holds ``(c_kv, k_rope)`` and the
  indexer's key a token, not K and V, and every query has a key set of its
  own, so prefill and decode both attend in the absorbed form over latents.
- **sliding layers**: latent attention of their own widths over the last
  ``sliding_window_size`` positions; their cache holds only the window.
- a headwise sigmoid gate on both kinds' output; the leading
  ``first_k_dense_replace`` layers a dense SwiGLU, the others a
  sigmoid-routed mixture (``noaux_tc``) with one shared expert, of which
  this model holds a share (``distributed/moe.held_moe``, as it stands).

Text only: the vision and audio towers and the MTP head of the published
model are no part of this file. There is no training forward.

**Served layer by layer, each layer's weights their own arrays**
(``models/tick.py``: the protocol ``ServingEngine`` asks of a model, and what
this file shares with ``models/deepseek_v2.py``). The forward is
``models/gpt.gpt_ragged_apply``'s sibling: the same flat token buffer and row
metadata, the pools (``serving.paged_cache.LatentPools``, stacked by kind of
layer and indexed by a static layer, written and read through their methods)
threaded through the layers in turn. The layers are unlike, so there is no
one block to scan; and
a scan over a run of like layers would slice each layer's held experts out of
a stack for the Pallas grouped matmul, a copy of 4.6 ms a matrix a tick on a
v5e (PERF.md section 6, PR 37). ``models/dots3_reference.py`` is the plain
float32 reference of the same equations; it reads this model's weights by the
names given here and none of its code.

What the configuration file does not settle, and how it is read here (each
a convention of the lineage): ``apply_mla_qkv_lora_rescale`` multiplies the
normed latents by ``sqrt(hidden / rank)`` (LongCat-Flash's reading of the
same flag) and the indexer takes the rescaled ``c_q``; the gate reads the
normed layer input; every full layer has an indexer, layer 0 too; no group
limit on the router (no ``n_group`` in the file); the indexer's Hadamard
rotation and fp8 are left out; a query sees ``s > t - window``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.moe import HeldMoEMLP, held_moe
from ..nn import initializer as I
from ..ops.latent_attention import select_threshold, selection_mask
from ..profiler.trace import annotate
from . import tick as _tick
from .gpt import rope_at
from .tick import (HeldExpertsConfig, LayerwiseLM, SwiGLUMLP, TickRows,
                   Weight, rms)

FULL, SLIDING = "full_attention", "sliding_attention"
#: what one tick reports beside its tokens, in this order (``aux["stats"]``)
TICK_STATS = ("selected_share", "expert_rows", "expert_load_max_over_mean",
              "experts_touched_share")
#: LayerNorm of the indexer's keys (DeepSeek-V3.2-Exp's inference code)
INDEX_NORM_EPS = 1e-6


@dataclass
class Dots3Config(HeldExpertsConfig):
    """Sizes under the names of the model's ``config.json``."""
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 46
    layer_types: Tuple[str, ...] = ()
    first_k_dense_replace: int = 1
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window_size: int = 513
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 1.0
    apply_mla_qkv_lora_rescale: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 524288
    initializer_range: float = 0.02
    #: the selection bias starts at 0 in the lineage and a balancing rule
    #: moves it; seeded weights that want it to matter set a deviation,
    #: small against the 0.005 between a token's 8th and 9th largest score
    #: (0.1 chose the experts whatever the token: PERF.md section 6, PR 37)
    select_bias_range: float = 0.0
    #: (first, count): the routed experts held here; None: all of them
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = tuple(
                FULL if i == 0 or i % 4 == 1 else SLIDING
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers {self.num_hidden_layers}")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is what is implemented")
        if self.routed_scaling_factor != 1:
            raise ValueError(
                "routed_scaling_factor other than 1: held_moe adds the "
                "shared expert to the routed sum it would have to scale")
        if self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("index_head_dim is under qk_rope_head_dim")

    def widths(self, kind: str) -> dict:
        pre = "swa_" if kind == SLIDING else ""
        get = lambda k: getattr(self, pre + k)          # noqa: E731
        return {"heads": get("num_attention_heads"),
                "q_rank": get("q_lora_rank"), "kv_rank": get("kv_lora_rank"),
                "nope": get("qk_nope_head_dim"),
                "rope": get("qk_rope_head_dim"), "v": get("v_head_dim"),
                "theta": float(get("rope_theta"))}

    def layer_params(self, layer: int) -> int:
        """Parameters of one layer as held here (the held experts alone)."""
        h = self.hidden_size
        kind = self.layer_types[layer]
        w = self.widths(kind)
        n = h * w["q_rank"] + w["q_rank"] \
            + w["q_rank"] * w["heads"] * (w["nope"] + w["rope"]) \
            + h * (w["kv_rank"] + w["rope"]) + w["kv_rank"] \
            + w["kv_rank"] * w["heads"] * (w["nope"] + w["v"]) \
            + w["heads"] * w["v"] * h + h * w["heads"] + 2 * h
        if kind == FULL:
            n += w["q_rank"] * self.index_n_heads * self.index_head_dim \
                + h * self.index_head_dim + 2 * self.index_head_dim \
                + h * self.index_n_heads
        if not self.is_moe(layer):
            return n + 3 * h * self.intermediate_size
        f = self.moe_intermediate_size
        return n + h * self.n_routed_experts + self.n_routed_experts \
            + 3 * h * f * (self.held[1] + 1)

    @staticmethod
    def dots3_note_prev():
        """The catalog row: 46 layers, 256 experts, 152,064 words."""
        return Dots3Config()

    @staticmethod
    def tiny(**kw):
        """Unit-test sizes: the leading dense layer and one period, a
        window, a selection and a page that a few dozen tokens cross."""
        base = dict(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=16, num_hidden_layers=5,
            layer_types=(FULL, FULL, SLIDING, SLIDING, SLIDING),
            num_attention_heads=4, q_lora_rank=16, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            swa_num_attention_heads=2, swa_q_lora_rank=16,
            swa_kv_lora_rank=12, swa_qk_nope_head_dim=12,
            swa_qk_rope_head_dim=4, swa_v_head_dim=8, sliding_window_size=5,
            index_n_heads=4, index_head_dim=8, index_topk=8,
            n_routed_experts=16, num_experts_per_tok=4,
            max_position_embeddings=256, initializer_range=0.2,
            select_bias_range=0.1)
        base.update(kw)
        return Dots3Config(**base)


class Dots3Attention(nn.Layer):
    """The weights of one layer's latent attention, ``kind`` its widths;
    a full layer's carry the indexer's."""

    def __init__(self, c: Dots3Config, kind: str):
        super().__init__()
        w, h = c.widths(kind), c.hidden_size
        init, one = I.Normal(0.0, c.initializer_range), I.Constant(1.0)
        self.q_a = Weight([h, w["q_rank"]], init)
        self.q_a_norm = Weight([w["q_rank"]], one)
        self.q_b = Weight(
            [w["q_rank"], w["heads"] * (w["nope"] + w["rope"])], init)
        self.kv_a = Weight([h, w["kv_rank"] + w["rope"]], init)
        self.kv_a_norm = Weight([w["kv_rank"]], one)
        self.kv_b = Weight(
            [w["kv_rank"], w["heads"] * (w["nope"] + w["v"])], init)
        self.o = Weight([w["heads"] * w["v"], h], init)
        self.gate = Weight([h, w["heads"]], init)
        if kind == FULL:
            self.idx_q = Weight(
                [w["q_rank"], c.index_n_heads * c.index_head_dim], init)
            self.idx_k = Weight([h, c.index_head_dim], init)
            self.idx_k_norm = Weight([c.index_head_dim], one, bias=True)
            self.idx_w = Weight([h, c.index_n_heads], init)


class Dots3Block(nn.Layer):
    def __init__(self, c: Dots3Config, layer: int):
        super().__init__()
        one = I.Constant(1.0)
        self.kind, self.moe = c.layer_types[layer], c.is_moe(layer)
        self.ln_1 = Weight([c.hidden_size], one)
        self.attn = Dots3Attention(c, self.kind)
        self.ln_2 = Weight([c.hidden_size], one)
        if self.moe:
            self.ffn = HeldMoEMLP(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, c.held,
                initializer_range=c.initializer_range,
                out_initializer_range=c.initializer_range,
                scoring="sigmoid", select_bias_range=c.select_bias_range,
                shared_width=c.moe_intermediate_size)
        else:
            self.ffn = SwiGLUMLP(c)


class Dots3(LayerwiseLM):
    """The served model: ``LayerwiseLM``'s weights, what caches it keeps and
    the tick's forward."""

    def __init__(self, config: Dots3Config):
        super().__init__(config, Dots3Block)

    # -- what ServingEngine asks of a model (models/tick.py) -------------
    def cache_spec(self) -> dict:
        c = self.config
        n_full = sum(k == FULL for k in c.layer_types)
        return {"kind": "latent", "full_layers": n_full,
                "latent_width": c.kv_lora_rank + c.qk_rope_head_dim,
                "index_width": c.index_head_dim,
                "window_layers": c.num_hidden_layers - n_full,
                "window_width": c.swa_kv_lora_rank + c.swa_qk_rope_head_dim,
                "window": c.sliding_window_size, "tick_record": TickRecord}

    def ragged_apply(self, stacked, other, pools, tokens, tok_pos, tok_limit,
                     row_tab, row_pos0, row_len, sample_ix, *, decode_rows,
                     chunk_width, has_chunks=None):
        return dots3_ragged_apply(
            self.config, stacked, other, pools, tokens, tok_pos, tok_limit,
            row_tab, row_pos0, row_len, sample_ix, decode_rows, chunk_width,
            has_chunks=has_chunks)


class TickRecord(_tick.TickRecord):
    STATS = TICK_STATS


# --------------------------------------------------------------------------
# the tick's forward
# --------------------------------------------------------------------------
def _ln(x, w, b, eps):
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, -1, keepdims=True)
    v = jnp.mean(jnp.square(xf - m), -1, keepdims=True)
    return ((xf - m) * jax.lax.rsqrt(v + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def _rope_part(x, pos, theta: float, lo: int, hi: int):
    """``x`` [NT, heads, d] with columns ``lo:hi`` rotated by ``pos``."""
    rot = rope_at(x[:, None, :, lo:hi], pos[:, None], theta)[:, 0]
    return jnp.concatenate([x[..., :lo], rot, x[..., hi:]], -1)


def _latent_queries(c: Dots3Config, kind: str, hn, p, pos):
    """What both attention kinds share, over the flat tokens ``hn`` [NT,
    h]: ``(c_q [NT, q_rank]``, the absorbed queries ``[NT, NH, C + R]``,
    the row to cache ``[NT, C + R]``, the gate ``[NT, NH])``."""
    w = c.widths(kind)
    nh, nope, rd = w["heads"], w["nope"], w["rope"]
    r_q = math.sqrt(c.hidden_size / w["q_rank"]) \
        if c.apply_mla_qkv_lora_rescale else 1.0
    r_kv = math.sqrt(c.hidden_size / w["kv_rank"]) \
        if c.apply_mla_qkv_lora_rescale else 1.0
    eps = c.rms_norm_eps
    c_q = rms(hn @ p["attn.q_a.weight"], p["attn.q_a_norm.weight"], eps)
    c_q = (c_q * r_q).astype(hn.dtype)
    q = (c_q @ p["attn.q_b.weight"]).reshape(-1, nh, nope + rd)
    q_rope = _rope_part(q[..., nope:], pos, w["theta"], 0, rd)
    kv = hn @ p["attn.kv_a.weight"]
    c_kv = rms(kv[:, :w["kv_rank"]], p["attn.kv_a_norm.weight"], eps)
    c_kv = (c_kv * r_kv).astype(hn.dtype)
    k_rope = _rope_part(kv[:, None, w["kv_rank"]:], pos, w["theta"], 0,
                        rd)[:, 0]
    # absorbed: q_nope carried into the latent space by W_kvb's key half
    w_k = p["attn.kv_b.weight"].reshape(w["kv_rank"], nh,
                                        nope + w["v"])[..., :nope]
    q_lat = jnp.einsum("tnd,cnd->tnc", q[..., :nope], w_k)
    gate = jax.nn.sigmoid((hn @ p["attn.gate.weight"]).astype(jnp.float32))
    return (c_q, jnp.concatenate([q_lat, q_rope], -1),
            jnp.concatenate([c_kv, k_rope], -1), gate)


def _attention_out(c: Dots3Config, kind: str, x, o_lat, gate, p):
    """The values carried out of the latent space, gated, projected and
    added to the residual stream."""
    w = c.widths(kind)
    w_v = p["attn.kv_b.weight"].reshape(
        w["kv_rank"], w["heads"], w["nope"] + w["v"])[..., w["nope"]:]
    o = jnp.einsum("tnc,cnd->tnd", o_lat.astype(x.dtype), w_v)
    o = (o * gate[..., None].astype(o.dtype)).reshape(o.shape[0], -1)
    return x + o @ p["attn.o.weight"]


def dots3_ragged_apply(c: Dots3Config, stacked, other, pools, tokens,
                       tok_pos, tok_limit, row_tab, row_pos0, row_len,
                       sample_ix, decode_rows: int, chunk_width: int,
                       has_chunks=None):
    """Mixed prefill/decode forward over latent and windowed pools: the
    arguments of ``gpt_ragged_apply``, with ``pools`` a ``LatentPools`` and
    ``row_tab`` the pair ``(tables of the full layers' pages, tables of the
    windowed layers' pages)``, both ``[R, NPs]``, ``stacked`` the layers'
    own weights (``{"layer<i>": {...}}``). The spelling of the full layers'
    attention is picked where this is traced (``ops/latent_attention.
    latent_attention_path``: the Pallas kernel on the chip at the published
    widths); every other read has one spelling. ``has_chunks`` is taken and
    not used: one body whatever the mix.

    Returns ``(logits [S, V], pools, aux)``: ``aux["stats"]`` float32
    ``[len(TICK_STATS)]`` (the mean share of its visible keys a live query
    selected, the rows the held experts were given a layer, their fullest
    over their mean, the share of them with a row), ``aux["selected"]``
    ``[full layers, S, capacity]`` bool the positions each sampled row
    selected (the mask its attention applied), ``aux["routed"]``
    ``[expert layers, S, top_k]`` int32 the experts each sampled row chose,
    ``aux["top_logit"]`` ``[S]`` float32 the sampled rows' largest logit and
    ``aux["window_lse"]`` ``[sliding layers, S]`` float32 the log of the sum
    of each sampled row's exponentiated scores in a sliding layer, mean over
    its heads (``ops/latent_attention.window_latent_attention``)."""
    del has_chunks
    tab, wtab = row_tab
    nt, nd, w = tokens.shape[0], decode_rows, chunk_width
    ps = pools.page_size
    nps = tab.shape[1]
    eps = c.rms_norm_eps
    topk = min(c.index_topk, nps * ps)
    with annotate("tick/embed"):
        x = other["embeddings.wte.weight"][tokens]              # [NT, h]
    rows_ = TickRows(ps, nps, tok_pos, tok_limit, row_pos0, nt, nd, w)
    page, wpage = rows_.page_of(tab), rows_.page_of(wtab)
    off = tok_pos % ps
    wrote, wwrote = rows_.touched(page, tab), rows_.touched(wpage, wtab)
    live = rows_.live(tab, row_len)
    groups = rows_.groups

    def full_attention(x, pl, p, layer):
        with annotate("blk/qkv"):
            hn = rms(x, p["ln_1.weight"], eps)
            c_q, q, row, gate = _latent_queries(c, FULL, hn, p, tok_pos)
            nj, dj, rd = c.index_n_heads, c.index_head_dim, \
                c.qk_rope_head_dim
            theta = float(c.rope_theta)
            q_i = _rope_part((c_q @ p["attn.idx_q.weight"]).reshape(
                nt, nj, dj), tok_pos, theta, 0, rd)
            k_i = _ln(hn @ p["attn.idx_k.weight"],
                      p["attn.idx_k_norm.weight"], p["attn.idx_k_norm.bias"],
                      INDEX_NORM_EPS)
            k_i = _rope_part(k_i[:, None], tok_pos, theta, 0, rd)[:, 0]
            w_i = (hn @ p["attn.idx_w.weight"]).astype(jnp.float32) \
                / math.sqrt(nj) / math.sqrt(dj)
        with annotate("blk/latent_scatter"):
            pl = pl.scatter_latent(layer, page, off, row, wrote) \
                .scatter_index(layer, page, off, k_i, wrote)
        with annotate("blk/index"):
            score = groups(lambda rows, cut: cut.flat(pl.index_scores(
                layer, cut(q_i), cut(w_i), tab[rows], row_pos0[rows],
                row_len[rows])))                            # [NT, cap]
        with annotate("blk/select"):
            keys, thr, ties = select_threshold(score, topk)
        # the sampled rows' sets, handed out as the mask the attention
        # applies (the same keys, threshold and ties; a query's visible
        # positions are those with a score)
        picked = selection_mask(
            keys[sample_ix], thr[sample_ix], ties[sample_ix]) \
            & (score[sample_ix] > -jnp.inf)
        with annotate("blk/attn/mla"):
            w_ = c.widths(FULL)
            o_lat = groups(lambda rows, cut: cut.flat(pl.attend_selected(
                layer, cut(q), tab[rows], row_pos0[rows], row_len[rows],
                cut(keys), cut(thr), cut(ties), w_["kv_rank"],
                1.0 / math.sqrt(w_["nope"] + w_["rope"]))))
        with annotate("blk/attn_out"):
            x = _attention_out(c, FULL, x, o_lat, gate, p)
        visible = (tok_pos + 1).astype(jnp.float32)
        share = jnp.sum(jnp.where(
            live, jnp.minimum(visible, topk) / visible, 0.0)) \
            / jnp.maximum(jnp.sum(live), 1)
        return x, pl, (share, picked)

    def sliding_attention(x, pl, p, layer):
        with annotate("blk/qkv"):
            hn = rms(x, p["ln_1.weight"], eps)
            _, q, row, gate = _latent_queries(c, SLIDING, hn, p, tok_pos)
        with annotate("blk/latent_scatter"):
            pl = pl.scatter_window(layer, wpage, off, row, wwrote)
        with annotate("blk/attn/swa"):
            w_ = c.widths(SLIDING)
            o_lat, lse = groups(lambda rows, cut: cut.flat(pl.attend_window(
                layer, cut(q), wtab[rows], row_pos0[rows], row_len[rows],
                c.sliding_window_size, w_["kv_rank"],
                1.0 / math.sqrt(w_["nope"] + w_["rope"]))))
        with annotate("blk/attn_out"):
            x = _attention_out(c, SLIDING, x, o_lat, gate, p)
        return x, pl, lse[sample_ix]

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
                scoring="sigmoid", select_bias=p["ffn.select_bias"],
                shared=(p["ffn.shared_gate"], p["ffn.shared_up"],
                        p["ffn.shared_down"]))
            rows = rows.astype(jnp.float32)
            # which experts the sampled rows chose (held_moe's own rule on
            # a dozen rows: the scores plus the bias select)
            score = jax.nn.sigmoid(jnp.dot(
                h2[sample_ix], p["ffn.gate"].astype(h2.dtype),
                preferred_element_type=jnp.float32))
            chosen = jax.lax.top_k(
                score + p["ffn.select_bias"].astype(jnp.float32),
                c.num_experts_per_tok)[1].astype(jnp.int32)
            return x + y.astype(x.dtype), ((jnp.stack([
                jnp.sum(rows), jnp.max(rows) / jnp.maximum(
                    jnp.mean(rows), 1e-9), jnp.mean(rows > 0)]), chosen),)

    stats_sel, stats_moe, stats_win, n_full, n_slide = [], [], [], 0, 0
    for i, kind in enumerate(c.layer_types):
        p = stacked[f"layer{i}"]
        if kind == FULL:
            x, pools, a = full_attention(x, pools, p, n_full)
            n_full += 1
            stats_sel.append(a)
        else:
            x, pools, a = sliding_attention(x, pools, p, n_slide)
            n_slide += 1
            stats_win.append(a)
        x, f = ffn(x, p, c.is_moe(i))
        stats_moe.extend(f)
    with annotate("tick/head"):
        last = rms(x[sample_ix], other["ln_f.weight"], eps)
        logits = last @ other["lm_head.weight"]                 # [S, V]
        top = jnp.max(logits.astype(jnp.float32), -1)
    share = jnp.mean(jnp.stack([s for s, _ in stats_sel])) if stats_sel \
        else jnp.zeros((), jnp.float32)
    per_moe = jnp.mean(jnp.stack([m for m, _ in stats_moe]), 0) \
        if stats_moe else jnp.zeros((3,), jnp.float32)
    routed = jnp.stack([r for _, r in stats_moe]) if stats_moe else \
        jnp.zeros((0, sample_ix.shape[0], c.num_experts_per_tok), jnp.int32)
    selected = jnp.stack([s for _, s in stats_sel]) if stats_sel \
        else jnp.zeros((0, sample_ix.shape[0], nps * ps), bool)
    window_lse = jnp.stack(stats_win) if stats_win else \
        jnp.zeros((0, sample_ix.shape[0]), jnp.float32)
    aux = {"stats": jnp.concatenate([share[None], per_moe]),
           "selected": selected, "routed": routed, "top_logit": top,
           "window_lse": window_lse}
    return logits, pools, aux
