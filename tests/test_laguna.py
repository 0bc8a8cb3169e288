"""Laguna at a small size (``tests/laguna_toy.py``) through ``ServingEngine``:
prompts on both sides of the window in chunks, then decode through the full
layers' and the windowed layers' grouped K/V pages, against the float32
reference ``models/laguna_reference.py`` on seeded weights; pages behind the
window freed and kept; the pool, its one window space and what it refuses;
the ticks with and without a chunk. The equations' side is
``tests/test_laguna_equations.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chunkless_tick import check_the_engines_count, conds_without_a_pool
from laguna_toy import PAGE, build, engine, reference, some_tokens
from paddle_tpu.models.laguna import (FULL, SLIDING, TICK_STATS,
                                      laguna_ragged_apply)
from paddle_tpu.profiler import metrics
from paddle_tpu.serving import paged_cache
from paddle_tpu.serving.paged_cache import (POOL_KINDS, LatentPagePool,
                                            WindowedKVPagePool,
                                            WindowedKVPools, WindowSpace,
                                            page_pool)


@pytest.fixture(scope="module")
def net():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return some_tokens()


def _against_reference(net, eng, rid, prompt, atol=3e-4):
    out = np.asarray(eng.tokens_so_far(rid))
    seq = np.concatenate([prompt, out[:-1]])
    want = reference(net, seq)["logits"][len(prompt) - 1:]
    np.testing.assert_array_equal(want.argmax(-1), out)
    np.testing.assert_allclose(
        np.asarray(eng.tick_record.top_logits(rid)), want.max(-1), atol=atol)
    return out


def _serve(net, tokens, **kw):
    """A prompt of three chunks of 8 (21 tokens, over three windows of 6)
    and one shorter than the window, decoded side by side."""
    eng = engine(net, **kw)
    assert eng.prefill_chunk == 8
    a = eng.submit(tokens[:21], 10)
    b = eng.submit(tokens[30:34], 6)
    eng.run()
    return eng, a, b


@pytest.fixture(scope="module")
def served(net, tokens):
    freed = metrics.registry().counter("serving/window_pages_freed")
    before = freed.value
    eng, a, b = _serve(net, tokens)
    return eng, a, b, freed.value - before


def test_the_engine_serves_the_references_logits_on_both_sides_of_the_window(
        net, tokens, served):
    eng, a, b, _ = served
    assert net.config.sliding_window == 6
    _against_reference(net, eng, a, tokens[:21])
    _against_reference(net, eng, b, tokens[30:34])
    assert eng.pool.check_consistency() == []
    reg = metrics.registry()
    for name in TICK_STATS[:-1]:
        assert reg.counter(
            "serving/tick_stat_sum{stat=%s}" % name).value > 0, name
    # the rows held_moe gave out are the rows the tick's own routing counts
    assert reg.counter("serving/tick_stat_sum{stat=%s}"
                       % TICK_STATS[-1]).value == 0
    routed = eng.tick_record.routed_experts(a)
    assert routed.shape == (10, 4, 3)
    seq = np.concatenate([tokens[:21], np.asarray(eng.tokens_so_far(a))[:-1]])
    want = np.stack(reference(net, seq)["routed"], 1)[20:]
    assert np.mean(np.sort(routed, -1) == np.sort(want, -1)) > 0.97


def test_pages_behind_the_window_are_freed_and_the_logits_do_not_change(
        net, tokens, served, monkeypatch):
    eng, a, b, freed = served
    # 21 + 10 positions under a window of 6 on pages of 4: all but the last
    # pages of the window went back as the frontier passed them
    assert freed >= 5
    assert eng.pool.window_pages_per_slot < eng.pool.pages_per_slot
    monkeypatch.setattr(WindowedKVPagePool, "FREE_BEHIND", False)
    counter = metrics.registry().counter("serving/window_pages_freed")
    before = counter.value
    kept, a2, b2 = _serve(net, tokens)
    assert counter.value == before
    assert kept.pool.window_pages_per_slot == kept.pool.pages_per_slot
    for x, y in ((a, a2), (b, b2)):
        assert eng.tokens_so_far(x) == kept.tokens_so_far(y)
        np.testing.assert_allclose(eng.tick_record.top_logits(x),
                                   kept.tick_record.top_logits(y), atol=1e-5)


def test_the_kernels_interpreted_serve_the_same_tokens(tokens,
                                                       attention_spelling):
    """Both kinds of attention through their Pallas kernels (interpreted) in
    every tick against the ``jax.numpy`` spellings."""
    net = build(num_hidden_layers=2, layer_types=(FULL, SLIDING))

    def serve():
        eng = engine(net)
        rid = eng.submit(tokens[:19], 5)
        return eng.run()[rid].tolist(), eng

    want, _ = serve()
    reg = metrics.registry()
    before = reg.counter("serving/attn_calls{path=pallas}").value
    attention_spelling("pallas")
    got, eng = serve()
    assert reg.counter("serving/attn_calls{path=pallas}").value > before
    assert got == want
    _against_reference(net, eng, 0, tokens[:19], atol=1e-3)


# --- a tick without a chunk ------------------------------------------------
def _a_tick(net, jit=True):
    """One tick by hand: three decode rows (slot 0 live at 9, the others
    empty) and the pad chunk row the engine sends."""
    stacked, other = net._decode_state()
    nps, w = 8, 8
    pool = WindowedKVPagePool(net.cache_spec(), 40, PAGE, 3, nps, w)
    pool.grow_slot(0, 3)
    tabs = tuple(jnp.asarray(t) for t in pool.row_tables([0, 1, 2, None]))
    rest = (jnp.arange(3 + w, dtype=jnp.int32) % 7,
            jnp.asarray([9, 0, 0] + [0] * w, jnp.int32),
            jnp.asarray([32, 32, 32] + [0] * w, jnp.int32), tabs,
            jnp.asarray([9, 0, 0, 0], jnp.int32),
            jnp.asarray([1, 1, 1, 0], jnp.int32),
            jnp.asarray([0, 0, 0], jnp.int32))

    def run(told, pools):
        return laguna_ragged_apply(net.config, stacked, other, pools, *rest,
                                   decode_rows=3, chunk_width=w,
                                   has_chunks=told)

    def tick(has_chunks, pools=pool.pools):
        if not jit:
            return run(has_chunks, pools)
        if has_chunks is None:
            return jax.jit(lambda p: run(None, p))(pools)
        return jax.jit(run)(jnp.asarray(has_chunks), pools)

    return tick, pool.pools


@pytest.mark.parametrize("told", [True, False])
def test_a_tick_whose_chunk_row_is_a_pad_is_one_tick_however_it_is_told(
        net, told):
    tick, _ = _a_tick(net)
    want, got = tick(None), tick(told)
    np.testing.assert_allclose(got[0], want[0], atol=1e-4)
    for x, y in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(x[:, 1:], y[:, 1:], atol=1e-4)
    # no pad token is routed: the live row's three choices, held or not
    stats = dict(zip(TICK_STATS, np.asarray(got[2]["stats"])))
    assert stats["decode_rows"] == 1 and stats["chunk_tokens"] == 0
    assert stats["expert_rows"] == 3 and stats["held_rows_unaccounted"] == 0
    assert stats["decode_keys"] == 10 and stats["window_decode_keys"] == 6


def test_no_cond_of_the_tick_takes_or_returns_a_pool(net):
    tick, pools = _a_tick(net, jit=False)
    assert conds_without_a_pool(tick, pools) \
        == net.config.num_hidden_layers + 1


def test_the_engine_counts_the_ticks_it_tells_have_no_chunk(net, tokens):
    check_the_engines_count(engine(net), tokens[:21], 12, chunks=3)


# --- the pool ----------------------------------------------------------------
def test_the_spec_builds_full_and_windowed_pages_over_one_set_of_heads(net):
    spec = net.cache_spec()
    assert spec["kind"] == "windowed_kv" and spec["window"] == 6
    assert (spec["full_layers"], spec["window_layers"]) == (2, 3)
    pool = page_pool(spec, 41, PAGE, 3, 8, 8, jnp.float32, False, False)
    assert type(pool) is WindowedKVPagePool \
        and POOL_KINDS["windowed_kv"] is WindowedKVPagePool
    assert isinstance(pool.pools, WindowedKVPools)
    # ceil((6 - 1 + 8) / 4) + 2 pages a slot hold every window
    assert pool.window_pages_per_slot == 6
    assert pool.pools.kv.kv.shape == (2, 41, 4, PAGE, 16)
    assert pool.pools.window.kv.shape == (3, 3 * 6 + 1, 4, PAGE, 16)
    assert set(pool.pools.arrays()) == {"kv", "window"}
    assert set(pool.live_shares()) == {"kv", "window"}
    assert pool.grow_slot(0, 4) and pool.slot_window_pages(0) == 4
    full, windowed = pool.row_tables([0, None])
    assert full.shape == windowed.shape == (2, 8)
    assert pool.free_behind(0, 14) == 2 and pool.slot_window_pages(0) == 2
    assert (windowed[0, :2] > 0).all() \
        and (pool.row_tables([0])[1][0, :2] == 0).all()
    assert pool.check_consistency() == []
    pool.release_slot(0)
    assert pool.window_allocator.num_allocated == 0
    assert pool.check_consistency() == []


def test_the_window_space_is_written_once():
    """The latent pool and the K/V pool take their second page space from
    one class; neither writes a method of it again."""
    for kind in (LatentPagePool, WindowedKVPagePool):
        assert kind.__mro__[1] is WindowSpace
        for name in ("grow_slot", "free_behind", "release_slot",
                     "row_tables", "live_shares", "check_consistency"):
            assert name not in vars(kind), (kind, name)
    assert paged_cache.LatentPagePool.FREE_BEHIND is True


@pytest.mark.parametrize("how,match", [
    (dict(prefix_cache=True), "window has passed"),
    (dict(dtype=jnp.int8), "int8 grouped pages"),
    (dict(rewinds=True), "verify tick"),
])
def test_what_the_pool_cannot_do_it_refuses_by_name(net, how, match):
    args = dict(dtype=jnp.float32, prefix_cache=False, rewinds=False)
    args.update(how)
    with pytest.raises(NotImplementedError, match=match):
        page_pool(net.cache_spec(), 41, PAGE, 3, 8, 8, args["dtype"],
                  args["prefix_cache"], args["rewinds"])


def test_a_handoff_and_a_rewind_are_refused_in_the_pools_words(net):
    pool = page_pool(net.cache_spec(), 41, PAGE, 3, 8, 8, jnp.float32, False,
                     False)
    with pytest.raises(NotImplementedError, match="export_held.*K and V"):
        pool.require("handoff", "export_held (a KV handoff)")
    with pytest.raises(NotImplementedError, match="full and windowed K/V"):
        pool.shrink_slot(0, 1)
    with pytest.raises(NotImplementedError, match="full and windowed K/V"):
        pool.share_into_slot(0, [1])
