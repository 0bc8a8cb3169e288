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

**Served layer by layer, each layer's weights their own arrays.**
``ServingEngine`` asks a model what caches it keeps (``cache_spec``), for its
weights as the tick reads them (``_decode_state``: ``{"layer<i>": {name:
array}}``, nothing stacked) and for the tick's forward (``ragged_apply``).
The forward is ``models/gpt.gpt_ragged_apply``'s sibling: the same flat token
buffer and row metadata, the pools (``serving.paged_cache.LatentPools``,
stacked by kind of layer and indexed by a static layer) threaded through the
layers in turn. The layers are unlike, so there is no one block to scan; and
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
import time
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..distributed.moe import HeldMoEMLP, held_moe
from ..nn import initializer as I
from ..ops import paged_attention as _pa
from ..profiler import registry as _registry
from ..profiler import trace as _ptrace
from ..profiler.trace import annotate
from .gpt import _rms, rope_at

FULL, SLIDING = "full_attention", "sliding_attention"
#: what one tick reports beside its tokens, in this order (``aux["stats"]``)
TICK_STATS = ("selected_share", "expert_rows", "expert_load_max_over_mean",
              "experts_touched_share")
#: LayerNorm of the indexer's keys (DeepSeek-V3.2-Exp's inference code)
INDEX_NORM_EPS = 1e-6


@dataclass
class Dots3Config:
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

    # the engine's names for what it reads of any served model
    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    def widths(self, kind: str) -> dict:
        pre = "swa_" if kind == SLIDING else ""
        get = lambda k: getattr(self, pre + k)          # noqa: E731
        return {"heads": get("num_attention_heads"),
                "q_rank": get("q_lora_rank"), "kv_rank": get("kv_lora_rank"),
                "nope": get("qk_nope_head_dim"),
                "rope": get("qk_rope_head_dim"), "v": get("v_head_dim"),
                "theta": float(get("rope_theta"))}

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

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

    def num_params(self) -> int:
        return sum(self.layer_params(i)
                   for i in range(self.num_hidden_layers)) \
            + 2 * self.vocab_size * self.hidden_size + self.hidden_size

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


class _Weight(nn.Layer):
    """One matrix ``[rows, cols]`` or vector, ``weight`` (and ``bias``)."""

    def __init__(self, shape, init, bias=False):
        super().__init__()
        self.weight = self.create_parameter(list(shape),
                                            default_initializer=init)
        if bias:
            self.bias = self.create_parameter(
                list(shape), default_initializer=I.Constant(0.0))


class Dots3Attention(nn.Layer):
    """The weights of one layer's latent attention, ``kind`` its widths;
    a full layer's carry the indexer's."""

    def __init__(self, c: Dots3Config, kind: str):
        super().__init__()
        w, h = c.widths(kind), c.hidden_size
        init, one = I.Normal(0.0, c.initializer_range), I.Constant(1.0)
        self.q_a = _Weight([h, w["q_rank"]], init)
        self.q_a_norm = _Weight([w["q_rank"]], one)
        self.q_b = _Weight(
            [w["q_rank"], w["heads"] * (w["nope"] + w["rope"])], init)
        self.kv_a = _Weight([h, w["kv_rank"] + w["rope"]], init)
        self.kv_a_norm = _Weight([w["kv_rank"]], one)
        self.kv_b = _Weight(
            [w["kv_rank"], w["heads"] * (w["nope"] + w["v"])], init)
        self.o = _Weight([w["heads"] * w["v"], h], init)
        self.gate = _Weight([h, w["heads"]], init)
        if kind == FULL:
            self.idx_q = _Weight(
                [w["q_rank"], c.index_n_heads * c.index_head_dim], init)
            self.idx_k = _Weight([h, c.index_head_dim], init)
            self.idx_k_norm = _Weight([c.index_head_dim], one, bias=True)
            self.idx_w = _Weight([h, c.index_n_heads], init)


class Dots3MLP(nn.Layer):
    def __init__(self, c: Dots3Config):
        super().__init__()
        init = I.Normal(0.0, c.initializer_range)
        self.fc_gate = _Weight([c.hidden_size, c.intermediate_size], init)
        self.fc_in = _Weight([c.hidden_size, c.intermediate_size], init)
        self.fc_out = _Weight([c.intermediate_size, c.hidden_size], init)


class Dots3Block(nn.Layer):
    def __init__(self, c: Dots3Config, layer: int):
        super().__init__()
        one = I.Constant(1.0)
        self.kind, self.moe = c.layer_types[layer], c.is_moe(layer)
        self.ln_1 = _Weight([c.hidden_size], one)
        self.attn = Dots3Attention(c, self.kind)
        self.ln_2 = _Weight([c.hidden_size], one)
        if self.moe:
            self.ffn = HeldMoEMLP(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, c.held,
                initializer_range=c.initializer_range,
                out_initializer_range=c.initializer_range,
                scoring="sigmoid", select_bias_range=c.select_bias_range,
                shared_width=c.moe_intermediate_size)
        else:
            self.ffn = Dots3MLP(c)


class _Embeddings(nn.Layer):
    def __init__(self, c: Dots3Config):
        super().__init__()
        self.wte = _Weight([c.vocab_size, c.hidden_size],
                           I.Normal(0.0, c.initializer_range))


class Dots3(nn.Layer):
    """The served model: weights, what caches it keeps and the tick's
    forward. ``forward(tokens [s])`` is one prefill of the whole sequence
    through pools of its own, float logits ``[s, vocab]``: for tests."""

    def __init__(self, config: Dots3Config):
        super().__init__()
        self.config = config
        self.embeddings = _Embeddings(config)
        self.blocks = nn.LayerList([Dots3Block(config, i)
                                    for i in range(config.num_hidden_layers)])
        self.ln_f = _Weight([config.hidden_size], I.Constant(1.0))
        self.lm_head = _Weight([config.hidden_size, config.vocab_size],
                               I.Normal(0.0, config.initializer_range))

    # -- what ServingEngine asks of a model -----------------------------
    def cache_spec(self) -> dict:
        c = self.config
        n_full = sum(k == FULL for k in c.layer_types)
        return {"kind": "latent", "full_layers": n_full,
                "latent_width": c.kv_lora_rank + c.qk_rope_head_dim,
                "index_width": c.index_head_dim,
                "window_layers": c.num_hidden_layers - n_full,
                "window_width": c.swa_kv_lora_rank + c.swa_qk_rope_head_dim,
                "window": c.sliding_window_size, "tick_record": TickRecord}

    def _decode_state(self):
        token = id(self.embeddings.wte.weight._value)
        cached = self.__dict__.get("_gen_state")
        if cached is None or cached[0] != token:
            cached = (token,) + _decode_state(self)
            self.__dict__["_gen_state"] = cached
        return cached[1], cached[2]

    def ragged_apply(self, stacked, other, pools, tokens, tok_pos, tok_limit,
                     row_tab, row_pos0, row_len, sample_ix, **kw):
        return dots3_ragged_apply(self.config, stacked, other, pools, tokens,
                                  tok_pos, tok_limit, row_tab, row_pos0,
                                  row_len, sample_ix, **kw)

    def forward(self, tokens):
        from ..serving.paged_cache import LatentPools

        toks = jnp.asarray(getattr(tokens, "_value", tokens),
                           jnp.int32).reshape(-1)
        s, ps = toks.shape[0], 8
        pages = -(-s // ps)
        stacked, other = self._decode_state()
        spec = self.cache_spec()
        pools = LatentPools.zeros(
            spec["full_layers"], pages + 1, spec["window_layers"], pages + 1,
            ps, spec["latent_width"], spec["index_width"],
            spec["window_width"], other["embeddings.wte.weight"].dtype)
        table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
        pos = jnp.arange(s, dtype=jnp.int32)
        logits, _, _ = dots3_ragged_apply(
            self.config, stacked, other, pools, toks, pos,
            jnp.full((s,), s, jnp.int32), (table, table),
            jnp.zeros((1,), jnp.int32), jnp.full((1,), s, jnp.int32), pos,
            decode_rows=0, chunk_width=s)
        return logits


class TickRecord:
    """What the ticks said of themselves (``dots3_ragged_apply``'s ``aux``),
    kept on the host: every drained tick's ``stats`` in the registry
    (``serving/tick_stat_sum{stat=}`` over ``serving/tick_stat_ticks``, and
    the latest under ``serving/tick_stat{stat=}``), and, for the requests a
    caller watches, what the rows that chose their tokens reported.
    ``ServingEngine`` makes one (``engine.tick_record``) and calls ``tick``
    for every tick it drains; a request that nobody watches costs nothing
    beyond the four ``stats``."""

    #: the names of ``aux["stats"]``, in order (a sibling model's record
    #: names its own)
    STATS = TICK_STATS

    def __init__(self):
        #: ``watch(rid)`` says whether request ``rid`` is recorded
        #: (default: every one; a caller with many requests sets a rule)
        self.watch = lambda rid: True
        self._by_rid: dict = {}

    def tick(self, aux: dict, positions, rids):
        """One drained tick: ``positions`` the cache position each sampled
        row's query stood at, ``rids`` the requests it emits for. Returns
        ``note(rid, row)`` for the engine to call for every token it hands
        to a request, or None where no watched request is among them."""
        reg = _registry()
        reg.counter("serving/tick_stat_ticks").add(1)
        for name, value in zip(self.STATS, np.asarray(aux["stats"])):
            reg.counter("serving/tick_stat_sum{stat=%s}" % name).add(
                float(value))
            reg.gauge("serving/tick_stat{stat=%s}" % name).set(float(value))
        if not any(self.watch(rid) for rid in rids):
            return None
        tops = np.asarray(aux["top_logit"])
        routed = np.asarray(aux["routed"])
        wlse = np.asarray(aux["window_lse"])

        def note(rid: int, row: int) -> None:
            if not self.watch(rid):
                return
            rec = self._by_rid.setdefault(rid, {
                "top": [], "routed": [], "lse": [], "selected": []})
            rec["top"].append(float(tops[row]))
            rec["routed"].append(routed[:, row])
            rec["lse"].append(wlse[:, row])
            # the first and the latest emitting row's sets, as the tick's
            # device array: nothing is fetched until ``selected_sets`` asks
            del rec["selected"][1:]
            rec["selected"].append((int(positions[row]), aux["selected"],
                                    row))

        return note

    def forget(self, keep) -> None:
        """Drops the records of requests not in ``keep``."""
        self._by_rid = {r: v for r, v in self._by_rid.items() if r in keep}

    def has(self, rid: int) -> bool:
        return rid in self._by_rid

    def top_logits(self, rid: int) -> Tuple[float, ...]:
        """The largest logit of the row that chose each token request
        ``rid`` has been handed."""
        return tuple(self._by_rid[rid]["top"])

    def selected_sets(self, rid: int) -> list:
        """``(query position, [the positions selected, ascending, a full
        layer each])`` of the rows that chose request ``rid``'s first and
        latest token: the mask its attention applied."""
        return [(pos, [np.flatnonzero(m) for m in np.asarray(sel[:, row])])
                for pos, sel, row in self._by_rid[rid]["selected"]]

    def routed_experts(self, rid: int):
        """``[tokens, expert layers, top_k]`` int32: the experts the row
        that chose each of request ``rid``'s tokens was routed to."""
        return np.stack(self._by_rid[rid]["routed"])

    def window_lse(self, rid: int):
        """``[tokens, sliding layers]`` float32: for the row that chose each
        of request ``rid``'s tokens, the log of the sum of its
        exponentiated scores in every sliding layer, mean over the heads."""
        return np.stack(self._by_rid[rid]["lse"])


def _decode_state(model: Dots3):
    """``(layers, other)``: ``layers["layer<i>"]`` the weights of layer
    ``i`` by their names within a block, ``other`` the rest by name. A
    model built under ``LazyGuard`` has no weights yet: they are drawn
    here, in one jitted call (``state_drawer``)."""
    from ..framework.lazy import is_abstract

    if any(is_abstract(p) for p in model.parameters()):
        from ..core import rng

        t_draw = time.perf_counter()
        state = jax.jit(state_drawer(model))(rng.next_key())
        _ptrace.charge_setup(
            "weights", time.perf_counter() - t_draw,
            sum(a.nbytes for a in jax.tree_util.tree_leaves(state)),
            where="device")
        return state
    per_block, rest = _state_names(model)
    return ({f"layer{i}": {n: p._value for n, p in zip(names, params)}
             for i, (names, params) in enumerate(per_block)},
            {n: p._value for n, p in rest})


def _state_names(model: Dots3):
    """``(names, parameters)`` of every block, and the rest by name."""
    from ..static.functional import state_tensors

    per_block = [state_tensors(b)[:2] for b in model.blocks]
    pn, pt, _, _ = state_tensors(model)
    block_ids = {id(x) for _, ts in per_block for x in ts}
    return per_block, [(n, p) for n, p in zip(pn, pt)
                       if id(p) not in block_ids]


def state_drawer(model: Dots3):
    """``key -> (layers, other)`` for a model whose parameters are
    ``LazyGuard``'s placeholders: every parameter drawn from its recorded
    initializer, in its own type, as the array the tick will read
    (``models/gpt._decode_state_drawer``'s sibling, for unlike layers)."""
    per_block, rest = _state_names(model)

    def draw(params, key):
        return [p._lazy_initializer(p._value.shape, p._value.dtype,
                                    jax.random.fold_in(key, j))
                for j, p in enumerate(params)]

    def drawer(key):
        keys = jax.random.split(key, len(per_block) + 1)
        layers = {f"layer{i}": dict(zip(names, draw(params, keys[i])))
                  for i, (names, params) in enumerate(per_block)}
        return layers, dict(zip([n for n, _ in rest],
                                draw([p for _, p in rest], keys[-1])))

    return drawer


class TickRows:
    """One tick's flat token buffer against its rows, as every latent tick
    forward reads it (``dots3_ragged_apply`` and ``models/deepseek_v2.py``'s
    sibling): ``nd`` decode rows of one token, then ``nch`` chunk rows of
    ``w``; ``ps`` the page size and ``nps`` the pages of a slot's table."""

    def __init__(self, ps: int, nps: int, tok_pos, tok_limit, row_pos0,
                 nt: int, nd: int, w: int):
        self.ps, self.nps, self.nd, self.w = ps, nps, nd, w
        self.nch = nch = (nt - nd) // w if w else 0
        self.row_pos0 = row_pos0
        parts = [jnp.arange(nd, dtype=jnp.int32)]
        if nch:
            parts.append(jnp.repeat(nd + jnp.arange(nch, dtype=jnp.int32),
                                    w))
        #: the row of each flat token
        self.tok_row = jnp.concatenate(parts)
        self._slot_page = jnp.minimum(tok_pos // ps, nps - 1)
        self._writes = tok_pos < tok_limit

    def page_of(self, table):
        """The page of ``table`` [R, NPs] each token writes to (the null
        page where it writes nothing)."""
        return jnp.where(self._writes,
                         table[self.tok_row, self._slot_page], 0)

    def touched(self, pages, table):
        """The pages this tick's tokens write to: each decode row's and
        the ``(w - 1) // ps + 2`` a chunk can span (null where there is
        none)."""
        nd, nch, w, ps, nps = self.nd, self.nch, self.w, self.ps, self.nps
        out = [pages[:nd]]
        if nch:
            lp = self.row_pos0[nd:nd + nch, None] // ps + jnp.arange(
                (w - 1) // ps + 2, dtype=jnp.int32)[None, :]
            out.append(jnp.where(lp < nps, jnp.take_along_axis(
                table[nd:nd + nch], jnp.minimum(lp, nps - 1), axis=1),
                0).reshape(-1))
        return jnp.concatenate(out)

    def live(self, table, row_len):
        """A token is live if its row holds it and the row a slot's pages
        (a free slot's decode row rides along on the null page): it is
        counted."""
        tok_ix = jnp.concatenate(
            [jnp.zeros((self.nd,), jnp.int32)]
            + [jnp.tile(jnp.arange(self.w, dtype=jnp.int32), self.nch)]
            * bool(self.nch))
        return (tok_ix < row_len[self.tok_row]) \
            & (table[self.tok_row, 0] > 0)

    def groups(self, fn):
        """``fn(rows, width)`` over the decode rows and the chunk rows,
        back in flat-token order."""
        outs = []
        if self.nd:
            outs.append(fn(slice(0, self.nd), 1))
        if self.nch:
            outs.append(fn(slice(self.nd, self.nd + self.nch), self.w))
        return jax.tree.map(lambda *a: jnp.concatenate(a, 0), *outs)


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
    c_q = _rms(hn @ p["attn.q_a.weight"], p["attn.q_a_norm.weight"], eps)
    c_q = (c_q * r_q).astype(hn.dtype)
    q = (c_q @ p["attn.q_b.weight"]).reshape(-1, nh, nope + rd)
    q_rope = _rope_part(q[..., nope:], pos, w["theta"], 0, rd)
    kv = hn @ p["attn.kv_a.weight"]
    c_kv = _rms(kv[:, :w["kv_rank"]], p["attn.kv_a_norm.weight"], eps)
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
                       impl=None, has_chunks=None):
    """Mixed prefill/decode forward over latent and windowed pools: the
    arguments of ``gpt_ragged_apply``, with ``pools`` a ``LatentPools`` and
    ``row_tab`` the pair ``(tables of the full layers' pages, tables of the
    windowed layers' pages)``, both ``[R, NPs]``, ``stacked`` the layers'
    own weights (``{"layer<i>": {...}}``). ``impl`` names the spelling of
    the full layers' attention (``ops/paged_attention.
    selected_latent_attention``: ``None`` for the one the platform and the
    shapes pick, the Pallas kernel on the chip at the published widths;
    ``"xla"`` / ``"pallas"``); every other read has one spelling.
    ``has_chunks`` is taken and not used: one body whatever the mix.

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
    its heads (``ops/paged_attention.window_latent_attention``)."""
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
            hn = _rms(x, p["ln_1.weight"], eps)
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
            pl = pl._replace(
                latent=_pa.latent_scatter(pl.latent, page, off, row, layer,
                                          wrote),
                index_k=_pa.latent_scatter(pl.index_k, page, off, k_i,
                                           layer, wrote))
        with annotate("blk/index"):
            def scores(rows, t):
                n = rows.stop - rows.start
                lo = rows.start if t == 1 else nd
                hi = lo + n * t
                return _pa.index_scores(
                    q_i[lo:hi].reshape(n, t, nj, dj),
                    w_i[lo:hi].reshape(n, t, nj), pl.index_k, layer,
                    tab[rows], row_pos0[rows], row_len[rows]
                ).reshape(n * t, nps * ps)

            score = groups(scores)                          # [NT, cap]
        with annotate("blk/select"):
            keys, thr, ties = _pa.select_threshold(score, topk)
        # the sampled rows' sets, handed out as the mask the attention
        # applies (the same keys, threshold and ties; a query's visible
        # positions are those with a score)
        picked = _pa.selection_mask(
            keys[sample_ix], thr[sample_ix], ties[sample_ix]) \
            & (score[sample_ix] > -jnp.inf)
        with annotate("blk/attn/mla"):
            w_ = c.widths(FULL)

            def attend(rows, t):
                n = rows.stop - rows.start
                lo = rows.start if t == 1 else nd
                hi = lo + n * t
                return _pa.selected_latent_attention(
                    q[lo:hi].reshape((n, t) + q.shape[1:]), pl.latent,
                    layer, tab[rows], row_pos0[rows], row_len[rows],
                    keys[lo:hi].reshape(n, t, -1), thr[lo:hi].reshape(n, t),
                    ties[lo:hi].reshape(n, t),
                    w_["kv_rank"], 1.0 / math.sqrt(w_["nope"] + w_["rope"]),
                    impl=impl
                ).reshape((n * t,) + q.shape[1:2] + (w_["kv_rank"],))

            o_lat = groups(attend)
        with annotate("blk/attn_out"):
            x = _attention_out(c, FULL, x, o_lat, gate, p)
        visible = (tok_pos + 1).astype(jnp.float32)
        share = jnp.sum(jnp.where(
            live, jnp.minimum(visible, topk) / visible, 0.0)) \
            / jnp.maximum(jnp.sum(live), 1)
        return x, pl, (share, picked)

    def sliding_attention(x, pl, p, layer):
        with annotate("blk/qkv"):
            hn = _rms(x, p["ln_1.weight"], eps)
            _, q, row, gate = _latent_queries(c, SLIDING, hn, p, tok_pos)
        with annotate("blk/latent_scatter"):
            pl = pl._replace(window=_pa.latent_scatter(
                pl.window, wpage, off, row, layer, wwrote))
        with annotate("blk/attn/swa"):
            w_ = c.widths(SLIDING)

            def attend(rows, t):
                n = rows.stop - rows.start
                lo = rows.start if t == 1 else nd
                o, lse = _pa.window_latent_attention(
                    q[lo:lo + n * t].reshape((n, t) + q.shape[1:]),
                    pl.window, layer, wtab[rows], row_pos0[rows],
                    row_len[rows], c.sliding_window_size, w_["kv_rank"],
                    1.0 / math.sqrt(w_["nope"] + w_["rope"]))
                return o.reshape((n * t,) + q.shape[1:2]
                                 + (w_["kv_rank"],)), lse.reshape(n * t)

            o_lat, lse = groups(attend)
        with annotate("blk/attn_out"):
            x = _attention_out(c, SLIDING, x, o_lat, gate, p)
        return x, pl, lse[sample_ix]

    def ffn(x, p, moe: bool):
        with annotate("blk/ffn"):
            h2 = _rms(x, p["ln_2.weight"], eps)
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
        last = _rms(x[sample_ix], other["ln_f.weight"], eps)
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
