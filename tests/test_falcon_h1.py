"""Falcon-H1 at a small size (``tests/falcon_h1_toy.py``) through
``ServingEngine``: a prompt in chunks, then decode through grouped K/V pages
and state slots **in the same layer**, against the float32 reference
``models/falcon_h1_reference.py`` on seeded weights: logits, every layer's
state, history and K/V; tenants of one slot in turn; the kernels interpreted;
the pool and what it refuses. The equations' side is
``tests/test_falcon_h1_equations.py``."""
import functools
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chunkless_tick import (a_tick, check_a_chunk_tick, check_a_pad_tick,
                            check_the_engines_count, conds_without_a_pool)
from falcon_h1_toy import PAGE, build, engine, reference, some_tokens
from paddle_tpu.models.falcon_h1 import (TICK_STATS, FalconH1Config,
                                         falcon_h1_ragged_apply)
from paddle_tpu.profiler import metrics
from paddle_tpu.serving.paged_cache import (POOL_KINDS, GroupedPools,
                                            SSDStatePools, StatePagePool,
                                            StatePools, page_pool)


@pytest.fixture(scope="module")
def net():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return some_tokens()


# --- through the engine ----------------------------------------------------
def _against_reference(net, eng, rid, prompt, atol=3e-4):
    out = np.asarray(eng.tokens_so_far(rid))
    seq = np.concatenate([prompt, out[:-1]])
    want = reference(net, seq)["logits"][len(prompt) - 1:]
    np.testing.assert_array_equal(want.argmax(-1), out)
    np.testing.assert_allclose(
        np.asarray(eng.tick_record.top_logits(rid)), want.max(-1), atol=atol)
    return out


def test_the_engine_serves_the_references_logits_through_its_pools(net,
                                                                   tokens):
    """A prompt in 3 chunks of 8 and then 20 decoded tokens: every emitted
    token is the reference's argmax, the tick's largest logit the
    reference's; a second request shares the ticks."""
    reg = metrics.registry()
    eng = engine(net)
    assert eng.prefill_chunk == 8
    a = eng.submit(tokens[:21], 20)
    b = eng.submit(tokens[30:43], 9)
    eng.run()
    _against_reference(net, eng, a, tokens[:21])
    _against_reference(net, eng, b, tokens[30:43])
    assert eng.pool.check_consistency() == []
    for name in TICK_STATS:
        assert reg.counter(
            "serving/tick_stat_sum{stat=%s}" % name).value > 0, name
    assert reg.counter("ssd/step_calls{path=xla}").value > 0
    assert reg.counter("ssd/chunk_calls{path=xla}").value > 0
    assert reg.counter("ssd/prep_calls{path=xla}").value > 0
    assert reg.gauge("serving/state_bytes").value == \
        eng.pool.pools.state.nbytes + eng.pool.pools.conv.nbytes


def test_a_live_slots_state_history_and_pages_are_the_references(net, tokens):
    """What the check reads: while a request is decoding, its slot's SSD
    state and convolution history in every layer, and its K/V rows, are the
    reference's after the tokens the slot holds."""
    eng = engine(net)
    rid = eng.submit(tokens[:21], 30)
    for _ in range(12):
        eng.step()
    eng.drain(0)
    slot, pos = eng.tick_record.stood_at(rid)
    out = np.asarray(eng.tokens_so_far(rid))
    seq = np.concatenate([tokens[:21], out])[:pos + 1]
    assert len(out) >= 5 and pos + 1 == 21 + len(out) - 1
    want = reference(net, seq)
    pools = eng.pool.pools
    pages = jnp.asarray(eng.pool.tables[slot][:-(-len(seq) // PAGE)])
    for layer in range(net.config.num_hidden_layers):
        got = pools.state_of(layer, jnp.asarray([slot + 1]), 4)[0]
        assert got.shape == (4, 8, 16)              # [heads, P, N]
        np.testing.assert_allclose(got, want["states"][layer], atol=2e-4,
                                   rtol=2e-3)
        np.testing.assert_allclose(pools.conv[layer, :, slot + 1],
                                   want["history"][layer], atol=1e-5,
                                   rtol=1e-5)
        k, v = pools.kv.rows_of(layer, pages)
        np.testing.assert_allclose(k[:len(seq)], want["keys"][layer],
                                   atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(v[:len(seq)], want["values"][layer],
                                   atol=1e-5, rtol=1e-4)


def test_a_slots_second_tenant_gives_what_a_fresh_engine_gives(net, tokens):
    fresh = engine(net, num_slots=1)
    rid = fresh.submit(tokens[30:45], 10)
    want = fresh.run()[rid]
    eng = engine(net, num_slots=1)
    eng.submit(tokens[:21], 14)
    eng.run()
    # the first tenant's state is still in the slot
    assert np.asarray(eng.pool.pools.state[:, 1]).any()
    rid = eng.submit(tokens[30:45], 10)
    assert eng.run()[rid].tolist() == want.tolist()
    _against_reference(net, eng, rid, tokens[30:45])


def test_two_requests_interleaved_give_what_each_gives_alone(net, tokens):
    alone = {}
    for a, n, new in ((0, 21, 12), (25, 10, 15)):
        eng = engine(net)
        rid = eng.submit(tokens[a:a + n], new)
        alone[(a, n, new)] = eng.run()[rid]
    eng = engine(net)
    rids = {}
    for key in alone:
        rids[eng.submit(tokens[key[0]:key[0] + key[1]], key[2])] = key
        eng.step()                      # admitted at different ticks
    outs = eng.run()
    for rid, key in rids.items():
        assert outs[rid].tolist() == alone[key].tolist(), key


def test_the_engine_emits_the_same_tokens_through_the_kernels(tokens,
                                                              monkeypatch,
                                                              attention_spelling):
    """The Pallas kernels interpreted in every tick (the SSD step, chunk and
    pass, substituted at ``ops/ssd``'s two seams, and grouped-query
    attention through the fixture) against the ``jax.numpy`` spellings: the
    same requests, the same tokens. Widths whose columns are whole lanes
    (conv over 128) and a chunk of one SSD block."""
    from paddle_tpu.ops import ssd

    net = build(mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
                num_hidden_layers=2)
    assert net.config.conv_width == 128

    def serve():
        eng = engine(net, num_slots=7, pages_per_slot=40, prefill_chunk=128)
        rid = eng.submit(np.tile(tokens, 3)[:135], 6)
        out = eng.run()[rid].tolist()
        assert eng.pool.check_consistency() == []
        return out, eng

    want, eng = serve()
    _against_reference(net, eng, 0, np.tile(tokens, 3)[:135], atol=1e-3)
    reg = metrics.registry()
    before = reg.counter("ssd/step_calls{path=pallas}").value
    monkeypatch.setattr(ssd, "ssd_path", lambda *a: "pallas")
    monkeypatch.setattr(ssd, "prep_path", lambda *a: "pallas")
    attention_spelling("pallas")
    got, eng = serve()
    assert reg.counter("ssd/step_calls{path=pallas}").value == before + 2
    assert got == want
    _against_reference(net, eng, 0, np.tile(tokens, 3)[:135], atol=1e-3)


# --- one tick, by hand -----------------------------------------------------
def test_the_ticks_statistics_and_its_dead_rows(net):
    """One tick of three decode rows (one live, one whose token has no page,
    one empty) and an empty chunk row."""
    cfg = net.config
    stacked, other = net._decode_state()
    nps, w = 8, 8
    pool = StatePagePool(net.cache_spec(), 40, PAGE, 3, nps, w)
    pool.grow_slot(0, 3)            # 12 positions: decoding at 9
    pool.grow_slot(1, 2)            # between chunks at 8: no page for 8
    pools = pool.pools._replace(
        state=pool.pools.state + 1.0, conv=pool.pools.conv + 1.0)
    tab, slots = pool.row_tables([0, 1, 2, None])
    assert slots.tolist() == [1, 2, 3, 0]
    tok_pos = jnp.asarray([9, 8, 0] + [0] * w, jnp.int32)
    limit = jnp.asarray([32, 32, 32] + [0] * w, jnp.int32)
    tick = jax.jit(functools.partial(          # one program, as a tick is
        falcon_h1_ragged_apply, cfg, decode_rows=3, chunk_width=w))
    _, after, aux = tick(
        stacked, other, pools, jnp.arange(3 + w, dtype=jnp.int32),
        tok_pos, limit, (jnp.asarray(tab), slots),
        jnp.asarray([9, 8, 0, 0], jnp.int32),
        jnp.asarray([1, 1, 1, 0], jnp.int32),
        jnp.asarray([0, 1, 2], jnp.int32))
    assert isinstance(after, SSDStatePools)
    stats = dict(zip(TICK_STATS, np.asarray(aux["stats"])))
    assert stats["live_state_rows"] == 1 and stats["chunk_tokens"] == 0
    assert stats["decode_keys"] == 10 and stats["chunk_keys"] == 0
    assert not np.array_equal(after.state[:, 1], pools.state[:, 1])
    for dead in (2, 3):
        np.testing.assert_array_equal(after.state[:, dead],
                                      pools.state[:, dead])
        np.testing.assert_array_equal(after.conv[:, :, dead],
                                      pools.conv[:, :, dead])
    # the live row's token went to its page at offset 1, in every layer
    page = int(tab[0, 2])
    assert np.asarray(after.kv.kv[:, page, :, 1]).any()
    assert not np.asarray(after.kv.kv[:, page, :, 2:]).any()


# --- a tick without a chunk (ISSUE 55; tests/chunkless_tick.py) -------------
@pytest.mark.parametrize("told", [True, False])
def test_a_tick_whose_chunk_row_is_a_pad_is_one_tick_however_it_is_told(
        net, told):
    check_a_pad_tick(net, falcon_h1_ragged_apply, told)


def test_a_tick_with_a_chunk_is_the_program_it_was(net):
    check_a_chunk_tick(net, falcon_h1_ragged_apply)


def test_no_cond_of_the_tick_takes_or_returns_a_pool(net):
    """A ``cond`` a dense stretch: one before the first layer, one between
    two layers, one after the last."""
    tick, pools = a_tick(net, falcon_h1_ragged_apply, chunk=False)
    assert conds_without_a_pool(tick, pools) \
        == net.config.num_hidden_layers + 1


def test_the_engine_counts_the_ticks_it_tells_have_no_chunk(net, tokens):
    """A prompt of three chunks of 8 and eleven more ticks."""
    check_the_engines_count(engine(net), tokens[:21], 12, chunks=3)


# --- the pool: pages and a state in every layer ----------------------------
def test_the_spec_builds_pages_and_a_state_in_every_layer(net):
    spec = net.cache_spec()
    assert spec["kind"] == "state" and spec["rule"] == "ssd"
    assert spec["layers"] == spec["state_layers"] == 3
    assert spec["key_value_heads"] == 2 and spec["heads"] == 4
    pool = page_pool(spec, 40, PAGE, 3, 8, 8, jnp.float32, False, False)
    assert isinstance(pool, StatePagePool) \
        and POOL_KINDS["state"] is StatePagePool
    assert isinstance(pool.pools, SSDStatePools) \
        and isinstance(pool.pools, StatePools)
    assert isinstance(pool.pools.kv, GroupedPools)
    # K's 2 heads and V's 2 in one array, a page's positions on the sublanes
    assert pool.pools.kv.kv.shape == (3, 40, 4, PAGE, 16)
    assert pool.pools.state.shape == (3, 4, 4, 16, 8)       # [.., H, N, P]
    assert pool.pools.conv.shape == (3, 3, 16, 32 + 2 * 2 * 16)
    assert pool.pools.state.dtype == jnp.float32
    assert set(pool.live_shares()) == {"kv", "state"}
    assert pool.grow_slot(1, 3) and pool.check_consistency() == []
    # the published widths: 18,432 B a token over nine layers, no padded head
    c = FalconH1Config(num_hidden_layers=9, vocab_size=32640)
    shape = GroupedPools.zeros(c.num_hidden_layers, 2, 16,
                               c.num_key_value_heads, c.head_dim,
                               jnp.bfloat16).kv.shape
    assert shape == (9, 2, 8, 16, 128)
    assert int(np.prod(shape)) * 2 // (2 * 16) == 18432


def test_what_a_state_cannot_do_is_still_refused(net, tokens):
    from paddle_tpu.serving.spec import SpecConfig

    with pytest.raises(NotImplementedError, match="states at that page's "
                       "boundary"):
        engine(net, prefix_cache=True)
    with pytest.raises(NotImplementedError, match="roll the state back"):
        engine(net, spec=SpecConfig(draft_model=net, k=2))
    with pytest.raises(NotImplementedError, match="int8 pages beside"):
        engine(net, kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="the second needs the "
                       "state the first leaves"):
        engine(net, prefill_chunks_per_tick=2)
    with pytest.raises(ValueError, match="not whole pages"):
        engine(net, prefill_chunk=6)
    eng = engine(net)
    with pytest.raises(NotImplementedError, match="not pages and nothing "
                       "ships them"):
        eng.submit(tokens[:5], 2, hold_after_prefill=True)
    assert set(StatePagePool.CANNOT) == {"prefix", "rewinds", "int8",
                                         "handoff", "chunk_rows"}
