"""What a served model is, and what every model with a tick of its own needs.

**The protocol.** ``serving.ServingEngine`` asks three things of a model, and
nothing else of it but ``model.config`` (``vocab_size``, ``max_seq_len``):

``cache_spec() -> dict``
    What caches serving keeps for it; ``serving.paged_cache.page_pool`` builds
    the pool from it. ``{"kind": "kv", "layers", "heads", "head_dim"}``: K and
    V a cache layer (``paged_cache.Pools``). ``{"kind": "latent",
    "full_layers", "latent_width"}`` and, where the model has them,
    ``"index_width"``, ``"window_layers"``, ``"window_width"``, ``"window"``:
    latent, indexer-key and windowed pools (``paged_cache.LatentPools``).
    ``{"kind": "windowed_kv", "full_layers", "window_layers", "window",
    "key_value_heads", "head_dim"}``: grouped K/V pages for the full layers
    and, in a page space that holds only the window, for the windowed ones,
    whatever the query heads of a layer (``paged_cache.WindowedKVPools``;
    ``row_tab`` is then the pair of page tables, as a latent model's).
    ``{"kind": "state", "layers", "heads", "head_dim", "state_layers",
    "state_heads", "key_dim", "value_dim", "conv_width", "conv_taps"}``: K and
    V pages for some layers and, for the others, a recurrent state and a short
    convolution's history a slot (``paged_cache.StatePools``; ``row_tab`` is
    then the page tables and each row's state slot). Two keys are the
    tick's: ``"loop_steps"`` (the times a tick runs the layers; 1 where absent) and ``"tick_record"`` (the class that reads the tick's
    ``aux`` on the host, below; absent: the tick reports nothing).
``_decode_state() -> (stacked, other)``
    The weights as the tick reads them, two pytrees of device arrays, cached
    until a weight changes. A model built under ``LazyGuard`` draws them here.
``ragged_apply(stacked, other, pools, tokens, tok_pos, tok_limit, row_tab,
row_pos0, row_len, sample_ix, *, decode_rows, chunk_width, has_chunks)
-> (logits [S, V], pools, aux)``
    One tick's forward over the flat token buffer (``models/gpt.
    gpt_ragged_apply`` documents the arguments; ``row_tab`` is what the pool's
    ``row_tables`` gives). It writes and reads the caches through ``pools``'
    methods alone, which pick the attention's spelling where the program is
    traced (``paged_attention.resolve_impl``, ``latent_attention_path``).
    ``aux`` is a dict of device arrays, what the tick says of itself beside
    its tokens: empty for a model that reports nothing, and then no output of
    the program.
    ``has_chunks`` is a bool scalar of the program (``None`` where a caller
    does not say: a test, ``LayerwiseLM.forward``): false on a tick none of
    whose chunk rows carries a token. The program is one body for every mix
    and such a tick's chunk rows ride as pad rows (``tok_limit`` 0, an
    all-null table, ``row_len`` 0: they write to the null page and slot and
    nothing samples them), so a forward may ignore it (``del has_chunks``:
    a model whose ticks all carry a chunk, or whose pads cost elsewhere).
    What it may do with it is skip work **on the pad rows alone, under a
    ``cond`` that neither takes nor returns ``pools``**: the pools are
    donated and updated in place, one body for every mix, and a ``cond``
    that carried them cost two whole-pool copies a tick on both branches
    (ROADMAP S3). ``gpt_ragged_apply`` skips the chunk rows' attention that
    way (its result, not the pools, leaves the ``cond``);
    ``TickRows.dense`` below runs a forward's row-wise stretches, between
    two calls on the pools, over the decode rows alone (Falcon-H1,
    Olmo-Hybrid: half of their ticks was products on 256 pad tokens).

**The record.** ``cache_spec()["tick_record"]()`` is made once an engine
(``engine.tick_record``). The engine calls ``tick(aux, positions, rids)`` for
every tick it drains and the ``note(rid, row)`` that returns for every token it
hands to a request, and ``forget(keep)`` when results are reset: ``TickRecord``
(a latent model's statistics, and what the sampled rows selected) and
``LoopRecord`` (a looped model's exit steps) below.

A model whose layers are one block repeated stacks its weights and scans
(``models/gpt.py``). A model of unlike layers keeps each layer's weights their
own arrays and threads the pools through its layers in turn (``models/
dots3.py``, ``models/deepseek_v2.py``, ``models/olmo_hybrid.py``, ``models/
ling3.py``, ``models/falcon_h1.py``, ``models/laguna.py``); what those share
is here:
``LayerwiseLM`` (the weights and their state), ``HeldExpertsConfig``,
``TickRows`` (the flat tokens against their rows) and ``rms``.
"""
from __future__ import annotations

import functools
import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn import initializer as I
from ..profiler import registry as _registry
from ..profiler import trace as _ptrace


def rms(x, w, eps):
    """``F.rms_norm``: float32 statistics and scale, cast back."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf / jnp.sqrt(ms + eps) * w.astype(jnp.float32)).astype(x.dtype)


class Weight(nn.Layer):
    """One matrix ``[rows, cols]`` or vector, ``weight`` (and ``bias``)."""

    def __init__(self, shape, init, bias=False):
        super().__init__()
        self.weight = self.create_parameter(list(shape),
                                            default_initializer=init)
        if bias:
            self.bias = self.create_parameter(
                list(shape), default_initializer=I.Constant(0.0))


class SwiGLUMLP(nn.Layer):
    """A dense SwiGLU of ``c.hidden_size`` x ``c.intermediate_size``."""

    def __init__(self, c):
        super().__init__()
        init = I.Normal(0.0, c.initializer_range)
        self.fc_gate = Weight([c.hidden_size, c.intermediate_size], init)
        self.fc_in = Weight([c.hidden_size, c.intermediate_size], init)
        self.fc_out = Weight([c.intermediate_size, c.hidden_size], init)


class Embeddings(nn.Layer):
    def __init__(self, c):
        super().__init__()
        self.wte = Weight([c.vocab_size, c.hidden_size],
                          I.Normal(0.0, c.initializer_range))


class HeldExpertsConfig:
    """What a configuration under ``config.json``'s names answers the same
    way in every such model (``layer_params(i)`` is the model's own)."""

    @property
    def max_seq_len(self) -> int:           # the engine's name for it
        return self.max_position_embeddings

    @property
    def held(self) -> Tuple[int, int]:
        """``(first, count)`` of the routed experts held here."""
        return self.experts_held or (0, self.n_routed_experts)

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    def num_params(self) -> int:
        return sum(self.layer_params(i)
                   for i in range(self.num_hidden_layers)) \
            + 2 * self.vocab_size * self.hidden_size + self.hidden_size


class LayerwiseLM(nn.Layer):
    """The skeleton of a language model served layer by layer: the weights
    (``embeddings``, ``blocks`` of ``block(config, i)``, ``ln_f``,
    ``lm_head``) and their state; a model adds ``cache_spec`` and
    ``ragged_apply``. ``forward(tokens [s])`` is one prefill of the whole
    sequence through pools of its own (``paged_cache.page_pool`` of its
    ``cache_spec()``, one slot), float logits ``[s, vocab]``: for tests."""

    def __init__(self, config, block):
        super().__init__()
        self.config = config
        self.embeddings = Embeddings(config)
        self.blocks = nn.LayerList([block(config, i)
                                    for i in range(config.num_hidden_layers)])
        self.ln_f = Weight([config.hidden_size], I.Constant(1.0))
        self.lm_head = Weight([config.hidden_size, config.vocab_size],
                              I.Normal(0.0, config.initializer_range))

    def _decode_state(self):
        """``(layers, other)``: ``layers["layer<i>"]`` the weights of block
        ``i`` by their names within it, ``other`` the rest by name; kept
        until the embedding is another array. A model built under
        ``LazyGuard`` has no weights yet: they are drawn here, in one jitted
        call (``state_drawer``)."""
        token = id(self.embeddings.wte.weight._value)
        cached = self.__dict__.get("_gen_state")
        if cached is None or cached[0] != token:
            cached = self.__dict__["_gen_state"] = (token,) + self._state()
        return cached[1], cached[2]

    def _state(self):
        from ..framework.lazy import is_abstract

        if any(is_abstract(p) for p in self.parameters()):
            from ..core import rng

            t_draw = time.perf_counter()
            state = jax.jit(state_drawer(self))(rng.next_key())
            _ptrace.charge_setup(
                "weights", time.perf_counter() - t_draw,
                sum(a.nbytes for a in jax.tree_util.tree_leaves(state)),
                where="device")
            return state
        per_block, rest = _state_names(self)
        return ({f"layer{i}": {n: p._value for n, p in zip(names, params)}
                 for i, (names, params) in enumerate(per_block)},
                {n: p._value for n, p in rest})

    def forward(self, tokens):
        from ..serving.paged_cache import page_pool

        toks = jnp.asarray(getattr(tokens, "_value", tokens),
                           jnp.int32).reshape(-1)
        s, ps = toks.shape[0], 8
        pages = -(-s // ps)
        w = pages * ps                  # the one chunk row: whole pages
        stacked, other = self._decode_state()
        pool = page_pool(self.cache_spec(), pages + 1, ps, 1, pages, w,
                         other["embeddings.wte.weight"].dtype, False, False)
        pool.grow_slot(0, pages)
        pos = jnp.arange(w, dtype=jnp.int32)
        # the tables of a tick of one (empty) decode row and the chunk row,
        # less the decode row
        row_tab = jax.tree.map(lambda a: jnp.asarray(a[1:]),
                               pool.row_tables([None, 0]))
        # one program, as a tick is (a forward dispatched operation by
        # operation compiles every one of them for itself), compiled once a
        # chunk width: jit keys its cache on the function it was given
        ticks = self.__dict__.setdefault("_forward_ticks", {})
        if w not in ticks:
            ticks[w] = jax.jit(functools.partial(
                self.ragged_apply, decode_rows=0, chunk_width=w))
        return ticks[w](
            stacked, other, pool.pools, jnp.pad(toks, (0, w - s)), pos,
            jnp.full((w,), s, jnp.int32), row_tab,
            jnp.zeros((1,), jnp.int32), jnp.full((1,), s, jnp.int32),
            pos[:s])[0]


def _state_names(model):
    """``(names, parameters)`` of every block, and the rest by name."""
    from ..static.functional import state_tensors

    per_block = [state_tensors(b)[:2] for b in model.blocks]
    pn, pt, _, _ = state_tensors(model)
    block_ids = {id(x) for _, ts in per_block for x in ts}
    return per_block, [(n, p) for n, p in zip(pn, pt)
                       if id(p) not in block_ids]


def state_drawer(model):
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
    """One tick's flat token buffer against its rows, as a forward of unlike
    layers reads it: ``nd`` decode rows of one token, then ``nch`` chunk rows
    of ``w``; ``ps`` the page size and ``nps`` the pages of a slot's table;
    ``has_chunks`` the protocol's (a bool scalar; ``None``: not told)."""

    def __init__(self, ps: int, nps: int, tok_pos, tok_limit, row_pos0,
                 nt: int, nd: int, w: int, has_chunks=None):
        self.ps, self.nps, self.nd, self.w = ps, nps, nd, w
        self.nch = nch = (nt - nd) // w if w else 0
        self.has_chunks = has_chunks
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

    def dense(self, fn, *arrays):
        """``fn(*arrays)`` for a ``fn`` that is row-wise (per-token arrays
        ``[n, ...]`` to a pytree of ``[n, ...]``: norms, products with a
        layer's matrices, gates, activations; the matrices it closes over).
        One of ``arrays`` is ``[NT, ...]`` or the list of its groups' parts
        as the pools leave them (``groups(fn, join=False)``: the decode
        rows' ``[nd, ...]``, then the chunk rows' ``[nch w, ...]``), joined
        where ``fn`` runs. Told that no chunk rides in this tick
        (``has_chunks`` false), it is ``fn`` over the ``nd`` decode rows
        alone and zeros for the chunk rows' ``nch w`` pad tokens, which
        nothing reads: a ``cond`` whose branches read each matrix once and
        give one shape. **``fn`` touches no pool**: a pool that a ``cond``
        takes or returns is copied whole (the module's docstring). Not told
        (``None``), or of one group only, it is ``fn`` over all rows and no
        ``cond``."""
        parts = lambda a: isinstance(a, (list, tuple))      # noqa: E731

        def all_rows(*arrays):
            return fn(*(jnp.concatenate(a, 0) if parts(a) else a
                        for a in arrays))

        if self.has_chunks is None or not (self.nd and self.nch):
            return all_rows(*arrays)
        nd, pad = self.nd, self.nch * self.w

        def decode_rows(*arrays):
            return jax.tree.map(
                lambda o: jnp.pad(o, [(0, pad)] + [(0, 0)] * (o.ndim - 1)),
                fn(*(a[0] if parts(a) else a[:nd] for a in arrays)))

        return jax.lax.cond(self.has_chunks, all_rows, decode_rows, *arrays)

    def groups(self, fn, join: bool = True):
        """``fn(rows, cut)`` over the decode rows and then the chunk rows, one
        call a width, back in flat-token order (with ``join`` false the
        groups' results as a list, for ``dense`` to join): ``rows`` the
        group's slice of the tick's rows (``n`` of them, ``t`` tokens each),
        ``cut(a)`` its tokens of a per-token array ``a`` [NT, ...] as ``[n, t, ...]`` and
        ``cut.flat(out)`` the way back, ``[n, t, ...]`` arrays as ``[n t,
        ...]``, which ``fn`` returns (inside its own scope: on the chip the
        reshape is a copy, and a trace charges it to the scope it is in)."""
        outs = []
        if self.nd:
            outs.append(fn(slice(0, self.nd), _Cut(0, self.nd, 1)))
        if self.nch:
            outs.append(fn(slice(self.nd, self.nd + self.nch),
                           _Cut(self.nd, self.nch, self.w)))
        if not join:
            return outs
        return jax.tree.map(lambda *a: jnp.concatenate(a, 0), *outs)


class _Cut:
    """One group's tokens of the flat buffer: ``n`` rows of ``t`` from
    ``lo``."""

    def __init__(self, lo: int, n: int, t: int):
        self.lo, self.n, self.t = lo, n, t

    def __call__(self, a):
        n, t = self.n, self.t
        return a[self.lo:self.lo + n * t].reshape((n, t) + a.shape[1:])

    def flat(self, out):
        return jax.tree.map(
            lambda o: o.reshape((self.n * self.t,) + o.shape[2:]), out)


def count_stats(names, stats) -> None:
    """One drained tick's ``stats`` (a device array, ``names`` in order) into
    the registry: ``serving/tick_stat_sum{stat=}`` over
    ``serving/tick_stat_ticks``, and the latest under
    ``serving/tick_stat{stat=}``."""
    reg = _registry()
    reg.counter("serving/tick_stat_ticks").add(1)
    for name, value in zip(names, np.asarray(stats)):
        reg.counter("serving/tick_stat_sum{stat=%s}" % name).add(float(value))
        reg.gauge("serving/tick_stat{stat=%s}" % name).set(float(value))


class TickRecord:
    """A latent model's ticks (``aux`` of ``models/dots3.dots3_ragged_apply``
    and its sibling): every drained tick's ``stats`` in the registry
    (``serving/tick_stat_sum{stat=}`` over ``serving/tick_stat_ticks``, and
    the latest under ``serving/tick_stat{stat=}``), and, for the requests a
    caller watches, what the rows that chose their tokens reported. A request
    that nobody watches costs nothing beyond the ``stats``."""

    #: the names of ``aux["stats"]``, in order (a model's record names its own)
    STATS: Tuple[str, ...] = ()

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
        count_stats(self.STATS, aux["stats"])
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


class LoopRecord:
    """A looped model's ticks (``aux["exit_steps"]`` float32 ``[2, S]``: the
    expected and the chosen exit step of each sampled row): their sums and
    count a request and over the rows sampled since ``exit_steps()`` last
    read them, whose means the gauges ``loop/expected_exit_step`` and
    ``loop/chosen_exit_step`` hold too."""

    def __init__(self):
        self._by_rid: dict = {}
        self._all = np.zeros(3)         # expected, chosen, rows

    def tick(self, aux: dict, positions, rids):
        exits = np.asarray(aux["exit_steps"], np.float64)
        expected = _registry().gauge("loop/expected_exit_step")
        chosen = _registry().gauge("loop/chosen_exit_step")

        def note(rid: int, row: int) -> None:
            one = np.append(exits[:, row], 1.0)
            self._by_rid[rid] = self._by_rid.get(rid, 0.0) + one
            self._all += one
            expected.set(self._all[0] / self._all[2])
            chosen.set(self._all[1] / self._all[2])

        return note

    def forget(self, keep) -> None:
        self._by_rid = {r: v for r, v in self._by_rid.items() if r in keep}

    def exit_steps(self, rid=None) -> Tuple[float, float, int]:
        """``(mean expected exit step, mean chosen exit step, rows)`` of the
        rows that chose request ``rid``'s tokens or, with none given, of
        every row sampled since this was last read that way."""
        if rid is not None:
            expected, chosen, n = self._by_rid.get(rid, np.zeros(3)).tolist()
        else:
            (expected, chosen, n), self._all = self._all.tolist(), np.zeros(3)
        return expected / max(n, 1), chosen / max(n, 1), int(n)
