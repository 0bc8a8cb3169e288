"""What the tests of a tick without a chunk share (ISSUE 55: Falcon-H1's and
Olmo-Hybrid's forwards hand ``has_chunks`` to ``models/tick.TickRows.dense``):
one tick by hand over a ``StatePagePool``, told or not whether a chunk rides
in it, what of two ticks' results must agree, the ``cond``s of the tick's
jaxpr, and an engine that notes what it tells each tick. The checks are
here, their cases in ``tests/test_falcon_h1.py`` and
``tests/test_olmo_hybrid.py``."""
import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.profiler import metrics
from paddle_tpu.serving.paged_cache import StatePagePool

PAGE = 4


def a_tick(net, ragged_apply, chunk: bool):
    """``(tick(has_chunks, pools=...), pools)`` over three decode rows (slot
    0 live at 9, slot 1 between two chunks, slot 2 empty) and a chunk row of
    8: slot 1's second chunk of six tokens where ``chunk``, else the pad row
    the engine sends. The dead rows sample the live one, as the engine's
    do; with a chunk, slot 1 samples its chunk's last token."""
    stacked, other = net._decode_state()
    nps, w = 8, 8
    pool = StatePagePool(net.cache_spec(), 40, PAGE, 3, nps, w)
    pool.grow_slot(0, 3)
    pool.grow_slot(1, 4 if chunk else 2)
    pools = pool.pools._replace(
        state=pool.pools.state + 1.0, conv=pool.pools.conv + 1.0)
    tab, slots = pool.row_tables([0, 1, 2, 1 if chunk else None])
    ch_pos = (8 + np.arange(w) if chunk else np.zeros(w, np.int64)).tolist()
    at = 16 if chunk else 8             # slot 1's decode row: no page there
    rest = (jnp.arange(3 + w, dtype=jnp.int32) % 7,
            jnp.asarray([9, at, 0] + ch_pos, jnp.int32),
            jnp.asarray([32, 32, 32] + [14 * chunk] * w, jnp.int32),
            (jnp.asarray(tab), slots),
            jnp.asarray([9, at, 0, 8 * chunk], jnp.int32),
            jnp.asarray([1, 1, 1, 6 * chunk], jnp.int32),
            jnp.asarray([0, 3 + 5 if chunk else 0, 0], jnp.int32))

    def raw(has_chunks, pools=pools):
        told = None if has_chunks is None else jnp.asarray(has_chunks)
        return ragged_apply(net.config, stacked, other, pools, *rest,
                            decode_rows=3, chunk_width=w, has_chunks=told)

    told_tick = jax.jit(raw)
    not_told = jax.jit(lambda pools: raw(None, pools))

    def tick(has_chunks, pools=pools):
        """One program a way of telling, as the engine's tick is one."""
        if has_chunks is None:
            return not_told(pools)
        return told_tick(jnp.asarray(has_chunks), pools)

    tick.raw = raw          # what ``conds_without_a_pool`` reads the jaxpr of
    return tick, pools


def tenants(pools):
    """Every leaf of the pools less the null page and the null slot, which
    the rows that carry nobody's token write whatever they hold to."""
    return [np.asarray(a[:, 1:]) for a in jax.tree_util.tree_leaves(
        (pools.kv, pools.state))] + [np.asarray(pools.conv[:, :, 1:])]


def assert_same_tick(got, want, atol=1e-4, null_rows=False):
    """Two ticks' ``(logits, pools, aux)`` agree: the sampled rows' logits,
    the tenants' pages, states and histories (with ``null_rows``, every
    leaf whole) and ``aux``."""
    leaves = jax.tree_util.tree_leaves if null_rows else tenants
    np.testing.assert_allclose(got[0], want[0], atol=atol)
    for a, b in zip(leaves(got[1]), leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=atol)
    np.testing.assert_array_equal(got[2]["stats"], want[2]["stats"])
    np.testing.assert_allclose(got[2]["top_logit"], want[2]["top_logit"],
                               atol=atol)


def conds_without_a_pool(tick, pools) -> int:
    """How many ``cond``s the tick told ``has_chunks`` holds; fails where
    one has an operand or a result of a pool's shape (a pool that a
    ``cond`` carries is copied whole, ROADMAP S3)."""
    raw = getattr(tick, "raw", tick)
    jaxpr = jax.make_jaxpr(lambda pl, told: raw(told, pl))(
        pools, jnp.asarray(False))
    shapes = {a.shape for a in jax.tree_util.tree_leaves(pools)}
    conds = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]
    for eqn in conds:
        for var in list(eqn.invars) + list(eqn.outvars):
            assert var.aval.shape not in shapes, var.aval
    return len(conds)


def check_a_pad_tick(net, ragged_apply, told: bool):
    """On a tick without a chunk the decode rows' products alone
    (``has_chunks`` false) give the sampled rows' logits, ``aux`` and every
    tenant's pages, state and history that all rows' products give, told
    (true) or not (``None``: the program before ISSUE 55)."""
    tick, before = a_tick(net, ragged_apply, chunk=False)
    want = tick(None)
    assert not np.array_equal(want[1].state[:, 1], before.state[:, 1])
    assert_same_tick(tick(told), want)


def check_a_chunk_tick(net, ragged_apply):
    """With a real chunk and ``has_chunks`` true the branch taken is the
    operations of the program that is not told, on all rows: the same
    logits (the chunk's last token sampled), ``aux`` and pools, the null
    page and slot with them."""
    tick, _ = a_tick(net, ragged_apply, chunk=True)
    want = tick(None)
    assert float(want[2]["stats"][1]) == 6          # the chunk's tokens
    assert_same_tick(tick(True), want, null_rows=True)


def check_the_engines_count(eng, prompt, new: int, chunks: int):
    """``serving/ticks_without_chunk`` beside ``serving/ticks`` over one
    request of ``chunks`` prefill chunks and ``new`` tokens: counted as the
    ticks were handed ``has_chunks`` false."""
    reg = metrics.registry()
    told, run_tick = [], eng._run_tick

    def spy(args):
        told.append(bool(args[-5]))     # _build_unified's ``has_chunks``
        return run_tick(args)

    eng._run_tick = spy
    ticks = reg.counter("serving/ticks").value
    without = reg.counter("serving/ticks_without_chunk").value
    eng.submit(prompt, new)
    eng.run()
    assert told.count(True) == chunks
    assert told.count(False) == new - 1     # the last chunk's tick emits one
    assert reg.counter("serving/ticks").value - ticks == len(told)
    assert reg.counter("serving/ticks_without_chunk").value - without \
        == new - 1
