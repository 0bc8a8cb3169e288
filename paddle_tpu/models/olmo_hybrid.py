"""Olmo-Hybrid (huggingface.co/allenai/Olmo-Hybrid-7B config.json,
``model_type`` ``olmo_hybrid``), served.

Layers of two kinds in periods of four (``layer_types``): three
``linear_attention`` (Gated DeltaNet, arXiv:2412.06464, as
``fla.layers.GatedDeltaNet`` builds it: a short convolution and SiLU over the
three projections, ``l2norm`` of q and k a head, a scalar decay and a
``beta`` in [0, 2] a head, a recurrent state ``S`` in ``R^{dk x dv}`` a head,
an output gate) and then one ``full_attention`` (30 heads of 128, RMSNorm
over the whole of q and of k, **no rotary**: ``rope_theta`` is null). Each
block is Olmo 2's, normed after its mixer and after its SwiGLU::

    h = x + RMSNorm(mixer(x)) ; y = h + RMSNorm(SwiGLU(h))

It is served layer by layer as the two latent models are (``models/tick.py``:
the protocol ``ServingEngine`` asks of a model, ``LayerwiseLM``,
``TickRows``), over caches of a third kind, ``serving.paged_cache.
StatePools``: K/V pages for the full layers and, for the linear ones, one
float32 state a slot, whatever the context, beside the three positions the
convolution looks back on. The forward touches them through the pools'
methods alone: ``scatter`` and ``attend`` as a K/V model does, ``prep``
(what lies between a linear layer's projections and its rule, over the
history a slot carries), ``step`` (decode rows: one token against a state)
and ``chunk`` (chunk rows: ``w`` tokens from a carried state). A tenant's first
chunk (``row_pos0 == 0``) enters at zero; a dead decode row (an empty slot,
a slot still prefilling) carries the null slot and touches no state.
``models/olmo_hybrid_reference.py`` is the plain float32 reference of the
same equations; it reads this model's weights by the names given here and
none of its code. There is no training forward.

What ``config.json`` does not settle, and how it is read here (the
configuration file's ``assumed``): the norms' placement and the hidden-wide
QK-norm are the Olmo 2/3 family's; no convolution bias; ``A_log`` and
``dt_bias`` are drawn as fla's initialiser draws them; q, k and v are one
matrix's columns ``[q | k | v]`` and ``a``, ``b`` another's (storage, not
mathematics).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn import initializer as I
from ..profiler.trace import annotate
from .tick import (LayerwiseLM, SwiGLUMLP, TickRows, Weight, count_stats,
                   rms)

_F32 = jnp.float32
#: the most queries of a chunk row one call of the full layers' attention
#: takes as a row (``olmo_hybrid_ragged_apply``'s ``attend``)
_ATTN_QUERIES = 32

#: what one tick reports beside its tokens, in this order (``aux["stats"]``)
TICK_STATS = ("live_state_rows", "chunk_tokens", "decode_keys", "chunk_keys",
              "chunk_pairs")


@dataclass
class OlmoHybridConfig:
    """Sizes under the names of the model's ``config.json``."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    #: each layer's kind, ``config.json``'s list; a model of fewer layers
    #: takes its first ``num_hidden_layers`` entries
    layer_types: Tuple[str, ...] = (
        ("linear_attention",) * 3 + ("full_attention",)) * 8
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(f"hidden_size {self.hidden_size} is not "
                             f"{self.num_attention_heads} heads")
        kinds = tuple(self.layer_types)[:self.num_hidden_layers]
        if len(kinds) < self.num_hidden_layers or set(kinds) - {
                "linear_attention", "full_attention"}:
            raise ValueError(
                f"layer_types {self.layer_types} for "
                f"{self.num_hidden_layers} layers of linear_attention or "
                "full_attention")
        self.layer_types = kinds
        if self.num_key_value_heads != self.num_attention_heads \
                or self.linear_num_key_heads != self.linear_num_value_heads:
            raise NotImplementedError(
                "grouped heads: this model has as many K/V heads as query "
                "heads, in both kinds of layer")

    @property
    def max_seq_len(self) -> int:           # the engine's name for it
        return self.max_position_embeddings

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_width(self) -> int:
        return 2 * self.key_width + self.value_width

    def layer_params(self, layer: int) -> int:
        h = self.hidden_size
        n = 3 * h * self.intermediate_size + 2 * h
        if self.layer_types[layer] == "full_attention":
            return n + 4 * h * h + 2 * h
        heads = self.linear_num_value_heads
        return n + h * self.conv_width + 2 * h * self.value_width \
            + 2 * h * heads + self.linear_conv_kernel_dim * self.conv_width \
            + 2 * heads + self.linear_value_head_dim

    def num_params(self) -> int:
        return sum(self.layer_params(i)
                   for i in range(self.num_hidden_layers)) \
            + 2 * self.vocab_size * self.hidden_size + self.hidden_size

    @staticmethod
    def olmo_hybrid_7b():
        """The catalog row: 32 layers in periods of four, 100,352 words."""
        return OlmoHybridConfig()

    @staticmethod
    def tiny(**kw):
        """Unit-test sizes: two periods of (two linear, one full), 6 heads
        of 24 x 48 beside 6 of 8."""
        base = dict(
            vocab_size=96, hidden_size=48, intermediate_size=64,
            num_hidden_layers=6, num_attention_heads=6,
            num_key_value_heads=6, linear_num_key_heads=6,
            linear_num_value_heads=6, linear_key_head_dim=24,
            linear_value_head_dim=48,
            layer_types=("linear_attention", "linear_attention",
                         "full_attention") * 2,
            max_position_embeddings=128, initializer_range=0.2)
        base.update(kw)
        return OlmoHybridConfig(**base)


class _LogUniform(I.Initializer):
    """``log(A)``, ``A`` uniform in (lo, hi): fla's ``A_log``."""

    def __init__(self, lo: float, hi: float):
        self.lo, self.hi = lo, hi

    def _init(self, shape, dtype, key):
        return jnp.log(jax.random.uniform(key, shape, _F32, self.lo,
                                          self.hi)).astype(dtype)


class _DtBias(I.Initializer):
    """fla's ``dt_bias``: ``dt`` log-uniform in [lo, hi], the bias its
    inverse softplus, ``dt + log(-expm1(-dt))``."""

    def __init__(self, lo: float = 1e-3, hi: float = 0.1):
        self.lo, self.hi = lo, hi

    def _init(self, shape, dtype, key):
        dt = jnp.exp(jax.random.uniform(
            key, shape, _F32, math.log(self.lo), math.log(self.hi)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class GatedDeltaNet(nn.Layer):
    """The weights of a linear layer's mixer."""

    def __init__(self, c: OlmoHybridConfig):
        super().__init__()
        h, heads = c.hidden_size, c.linear_num_value_heads
        init, one = I.Normal(0.0, c.initializer_range), I.Constant(1.0)
        taps = c.linear_conv_kernel_dim
        self.qkv = Weight([h, c.conv_width], init)      # [q | k | v]
        self.gate = Weight([h, c.value_width], init)
        self.ab = Weight([h, 2 * heads], init)          # [a | b]
        self.conv = Weight([taps, c.conv_width],
                           I.Uniform(-taps ** -0.5, taps ** -0.5))
        self.A_log = Weight([heads], _LogUniform(1e-3, 16.0))
        self.dt_bias = Weight([heads], _DtBias())
        self.o_norm = Weight([c.linear_value_head_dim], one)
        self.o = Weight([c.value_width, h], init)


class FullAttention(nn.Layer):
    """The weights of a full layer's mixer."""

    def __init__(self, c: OlmoHybridConfig):
        super().__init__()
        h = c.hidden_size
        init, one = I.Normal(0.0, c.initializer_range), I.Constant(1.0)
        self.qkv = Weight([h, 3 * h], init)             # [q | k | v]
        self.q_norm = Weight([h], one)
        self.k_norm = Weight([h], one)
        self.o = Weight([h, h], init)


class OlmoHybridBlock(nn.Layer):
    def __init__(self, c: OlmoHybridConfig, layer: int):
        super().__init__()
        one = I.Constant(1.0)
        self.full = c.layer_types[layer] == "full_attention"
        if self.full:
            self.attn = FullAttention(c)
        else:
            self.mix = GatedDeltaNet(c)
        self.ln_1 = Weight([c.hidden_size], one)        # after the mixer
        self.ffn = SwiGLUMLP(c)
        self.ln_2 = Weight([c.hidden_size], one)        # after the SwiGLU


class TickRecord:
    """This model's ticks (``aux`` of ``olmo_hybrid_ragged_apply``): every
    drained tick's ``stats`` in the registry (``models/tick.count_stats``, as
    a latent model's record does), for the requests a caller watches the
    largest logit of each row that chose a token, and for every request
    **where its latest token's row stood**: the slot, and the cache position
    of its query, after which the slot's states hold that position's token
    and all before it."""

    STATS = TICK_STATS

    def __init__(self):
        self.watch = lambda rid: True
        self._tops: dict = {}
        self._at: dict = {}

    def tick(self, aux: dict, positions, rids):
        count_stats(self.STATS, aux["stats"])
        tops = np.asarray(aux["top_logit"]) \
            if any(self.watch(rid) for rid in rids) else None

        def note(rid: int, row: int) -> None:
            self._at[rid] = (row, int(positions[row]))
            if tops is not None and self.watch(rid):
                self._tops.setdefault(rid, []).append(float(tops[row]))

        return note

    def forget(self, keep) -> None:
        self._tops = {r: v for r, v in self._tops.items() if r in keep}
        self._at = {r: v for r, v in self._at.items() if r in keep}

    def has(self, rid: int) -> bool:
        return rid in self._tops

    def top_logits(self, rid: int) -> Tuple[float, ...]:
        return tuple(self._tops[rid])

    def stood_at(self, rid: int):
        """``(slot, position)`` of the row that chose request ``rid``'s
        latest token, watched or not; None before its first."""
        return self._at.get(rid)


class OlmoHybrid(LayerwiseLM):
    """The served model: ``LayerwiseLM``'s weights, what caches it keeps and
    the tick's forward."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__(config, OlmoHybridBlock)

    # -- what ServingEngine asks of a model (models/tick.py) -------------
    def cache_spec(self) -> dict:
        c = self.config
        full = c.layer_types.count("full_attention")
        return {"kind": "state", "layers": full,
                "heads": c.num_attention_heads, "head_dim": c.head_dim,
                "state_layers": c.num_hidden_layers - full,
                "state_heads": c.linear_num_value_heads,
                "key_dim": c.linear_key_head_dim,
                "value_dim": c.linear_value_head_dim,
                "conv_width": c.conv_width,
                "conv_taps": c.linear_conv_kernel_dim,
                "tick_record": TickRecord}

    def ragged_apply(self, stacked, other, pools, tokens, tok_pos, tok_limit,
                     row_tab, row_pos0, row_len, sample_ix, *, decode_rows,
                     chunk_width, has_chunks=None):
        return olmo_hybrid_ragged_apply(
            self.config, stacked, other, pools, tokens, tok_pos, tok_limit,
            row_tab, row_pos0, row_len, sample_ix, decode_rows, chunk_width,
            has_chunks=has_chunks)


# --------------------------------------------------------------------------
# the tick's forward
# --------------------------------------------------------------------------
class _Layer(NamedTuple):
    """A layer as the tick's forward walks it: its weights, its index among
    the layers of its kind (the pools') and its kind's three functions:
    ``before(p, x) -> (what the pools take, what ``out`` keeps)``,
    ``pools(pools, p, ix, *taken) -> (o, pools)``, ``out(p, x, o, *kept)``."""
    p: dict
    ix: int
    before: Callable
    pools: Callable
    out: Callable


def _gates(c: OlmoHybridConfig, ab, p):
    """``(g, beta)`` float32 a head from the ``[a | b]`` projection."""
    heads = c.linear_num_value_heads
    a, b = ab[..., :heads].astype(_F32), ab[..., heads:].astype(_F32)
    g = -jnp.exp(p["mix.A_log.weight"].astype(_F32)) * jax.nn.softplus(
        a + p["mix.dt_bias.weight"].astype(_F32))
    beta = jax.nn.sigmoid(b)
    return g, 2.0 * beta if c.linear_allow_neg_eigval else beta


def olmo_hybrid_ragged_apply(c: OlmoHybridConfig, stacked, other, pools,
                             tokens, tok_pos, tok_limit, row_tab, row_pos0,
                             row_len, sample_ix, decode_rows: int,
                             chunk_width: int, has_chunks=None):
    """Mixed prefill/decode forward over ``StatePools``: the arguments of
    ``models/gpt.gpt_ragged_apply``, ``row_tab`` the pair ``(page tables [R,
    NPs], state slots [R])`` that ``StatePagePool.row_tables`` gives.

    A decode row is **live** if it carries a state slot and its token has a
    page of its slot to be written to; the others (an empty slot, a slot
    between two chunks of its prompt, a slot whose chunk row rides in this
    tick) take the null slot. A chunk row at position 0 is a tenant's
    first: it enters at a zero state and a zero history.

    **``has_chunks``** (``models/tick.py``: false on a tick whose chunk row
    is a pad) goes to ``TickRows.dense``: a layer's projections, gates and
    norms before the pools and its output matrix and SwiGLU after them are
    row-wise, so one layer's end and the next one's start are one stretch
    that such a tick runs over its decode rows alone. The calls on the
    pools (``linear_pools``, ``full_pools``) stay outside every ``cond``.

    Returns ``(logits [S, V], pools, aux)``: ``aux["stats"]`` float32
    ``[len(TICK_STATS)]`` (the live decode rows, the chunk rows' tokens, the
    keys the decode rows' and the chunk rows' full attention read a layer,
    the chunk rows' visible query-key pairs) and ``aux["top_logit"]`` ``[S]``
    float32, the sampled rows' largest logit."""
    tab, slots = row_tab
    nt, nd, w = tokens.shape[0], decode_rows, chunk_width
    ps, nps = pools.page_size, tab.shape[1]
    eps, heads = c.rms_norm_eps, c.num_attention_heads
    lin_heads, dv = c.linear_num_value_heads, c.linear_value_head_dim
    with annotate("tick/embed"):
        x = other["embeddings.wte.weight"][tokens]              # [NT, h]
    rows_ = TickRows(ps, nps, tok_pos, tok_limit, row_pos0, nt, nd, w,
                     has_chunks)
    page = rows_.page_of(tab)
    off = tok_pos % ps
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

    # A layer's rows meet the pools in the middle of it. What it makes of
    # them before (``*_before``: the projections, gates and norms) and after
    # (``after``: the output matrix and the SwiGLU) is row-wise and touches
    # no pool; what lies between (``*_pools``) is the pools' methods.
    def linear_before(p, x):
        with annotate("blk/gdn/proj"):
            qkv = x @ p["mix.qkv.weight"]
            gate = x @ p["mix.gate.weight"]
            g, beta = _gates(c, x @ p["mix.ab.weight"], p)
        return (qkv, g, beta), (gate,)

    def linear_pools(pl, p, layer, qkv, g, beta):
        taps = p["mix.conv.weight"]
        # between projections and rule, a row group a call: the pool's
        # history read and written inside it (StatePools.prep)
        prep = lambda rows, slots, **kw: pl.prep(           # noqa: E731
            layer, slots, rows, taps, c.linear_num_key_heads,
            c.linear_key_head_dim, **kw)
        outs = []
        if nd:
            with annotate("blk/gdn/prep"):
                q, k, v, pl = prep(qkv[:nd], dec_slots)
            with annotate("blk/gdn/step"):
                o, pl = pl.step(layer, dec_slots, q, k, v, g[:nd], beta[:nd])
            outs.append(o)
        if nch:
            cut = lambda a: a[nd:].reshape(                 # noqa: E731
                (nch, w) + a.shape[1:])
            with annotate("blk/gdn/prep"):
                q, k, v, pl = prep(cut(qkv), ch_slots, fresh=fresh,
                                   row_len=ch_len)
            with annotate("blk/gdn/chunk"):
                o, pl = pl.chunk(layer, ch_slots, fresh, ch_len, q, k, v,
                                 cut(g), cut(beta))
            outs.append(o.reshape(nch * w, lin_heads, dv))
        return outs, pl                 # [nd, H, dv], [nch w, H, dv] f32

    def linear_out(p, x, o, gate):
        with annotate("blk/gdn/out"):
            ms = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
            y = o * jax.lax.rsqrt(ms + eps) \
                * p["mix.o_norm.weight"].astype(_F32)
            y = y.reshape(o.shape[0], -1) * jax.nn.silu(gate.astype(_F32))
            return y.astype(x.dtype) @ p["mix.o.weight"]

    def full_before(p, x):
        with annotate("blk/qkv"):
            qkv = x @ p["attn.qkv.weight"]
            h = c.hidden_size
            q = rms(qkv[:, :h], p["attn.q_norm.weight"], eps)
            k = rms(qkv[:, h:2 * h], p["attn.k_norm.weight"], eps)
            split = lambda a: a.reshape(-1, 1, heads, c.head_dim)  # noqa
            return (split(q), split(k), split(qkv[:, 2 * h:])), ()

    def full_pools(pl, p, layer, q, k, v):
        del p                           # no weight lies between
        with annotate("blk/kv_scatter"):
            pl = pl.scatter(layer, page, off, k, v)

        def attend(rows, cut):
            # a chunk row attends in pieces of ``_ATTN_QUERIES``: the ragged
            # kernel keeps a row's scores, weights and accumulator for all
            # its heads in VMEM, and 64 queries of 32 head rows pass it (a
            # later piece sees the earlier ones' keys: all are written)
            n, t = cut.n, cut.t
            pieces = t // _ATTN_QUERIES if t % _ATTN_QUERIES == 0 else 1
            first = jnp.tile(jnp.arange(pieces, dtype=jnp.int32)
                             * (t // pieces), n)
            rep = lambda a: jnp.repeat(a[rows], pieces, axis=0)  # noqa: E731
            with annotate("blk/attn"):
                o = pl.attend(
                    layer, cut(q[:, 0]).reshape(
                        n * pieces, t // pieces, heads, c.head_dim),
                    rep(tab), rep(row_pos0) + first,
                    jnp.clip(rep(row_len) - first, 0, t // pieces))
                return cut.flat(o.reshape(n, t, heads, c.head_dim))

        return rows_.groups(attend, join=False), pl

    def full_out(p, x, o):
        with annotate("blk/attn_out"):
            return o.reshape(o.shape[0], -1).astype(x.dtype) \
                @ p["attn.o.weight"]

    def after(layer, x, o, *kept):
        p = layer.p
        out = layer.out(p, x, o, *kept)
        with annotate("blk/ffn"):
            x = x + rms(out, p["ln_1.weight"], eps)
            mid = jax.nn.silu(x @ p["ffn.fc_gate.weight"]) \
                * (x @ p["ffn.fc_in.weight"])
            return x + rms(mid @ p["ffn.fc_out.weight"], p["ln_2.weight"],
                           eps)

    def between(layer, nxt, x, o, *kept):
        x = after(layer, x, o, *kept)
        return x, nxt.before(nxt.p, x)

    kinds = {"full_attention": (full_before, full_pools, full_out),
             "linear_attention": (linear_before, linear_pools, linear_out)}
    layers, seen = [], dict.fromkeys(kinds, 0)
    for i, kind in enumerate(c.layer_types):
        layers.append(_Layer(stacked[f"layer{i}"], seen[kind], *kinds[kind]))
        seen[kind] += 1
    # the dense stretch between two layers' pool calls is one layer's end
    # and the next one's start: one ``rows_.dense`` each (its own scope
    # names the branch itself, whose turnaround is the dense part's)
    with annotate("blk/ffn"):
        to_pools, kept = rows_.dense(
            partial(layers[0].before, layers[0].p), x)
    for layer, nxt in zip(layers, layers[1:] + [None]):
        o, pools = layer.pools(pools, layer.p, layer.ix, *to_pools)
        with annotate("blk/ffn"):
            if nxt is None:
                x = rows_.dense(partial(after, layer), x, o, *kept)
            else:
                x, (to_pools, kept) = rows_.dense(
                    partial(between, layer, nxt), x, o, *kept)
    with annotate("tick/head"):
        last = rms(x[sample_ix], other["ln_f.weight"], eps)
        logits = last @ other["lm_head.weight"]                 # [S, V]
        top = jnp.max(logits.astype(_F32), -1)
    return logits, pools, {"stats": stats, "top_logit": top}
