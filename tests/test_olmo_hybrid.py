"""Olmo-Hybrid at a small size (two periods of two linear layers and a full
one, 6 heads of 24 x 48 beside 6 of 8, pages of 4): the whole stack and the
engine (a prompt in chunks, then decode through K/V pages and state slots)
against the float32 reference ``models/olmo_hybrid_reference.py`` on seeded
weights; tenants of one engine and of one slot; preemption; the pool of a
state and what it refuses."""
import dataclasses

import functools
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from chunkless_tick import (a_tick, check_a_chunk_tick, check_a_pad_tick,
                            check_the_engines_count, conds_without_a_pool)
from paddle_tpu.models import olmo_hybrid_reference as ref
from paddle_tpu.models.olmo_hybrid import (TICK_STATS, OlmoHybrid,
                                           OlmoHybridConfig,
                                           olmo_hybrid_ragged_apply)
from paddle_tpu.profiler import metrics
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.paged_cache import (POOL_KINDS, StatePagePool,
                                            StatePools, page_pool)

PAGE = 4


def build(seed=5, **kw):
    paddle.seed(seed)
    net = OlmoHybrid(OlmoHybridConfig.tiny(**kw))
    net.eval()
    # at this size's initializer_range a token's ``g`` would pass the chunked
    # form's floor (``ops/kda.G_MIN``; tests/test_gdn_ops.py holds what the
    # floor does): decays of A <= 0.4 stay above it
    for block in net.blocks:
        if not block.full:
            a_log = block.mix.A_log.weight
            a_log._value = jnp.minimum(a_log._value, np.log(0.4))
    return net


def layers_of(net):
    layers, _ = net._decode_state()
    for i, kind in enumerate(net.config.layer_types):
        yield kind, layers[f"layer{i}"]


def reference(net, tokens, control=None, **kw):
    other = net._decode_state()[1]
    got = ref.forward(layers_of(net), other, tokens,
                      dataclasses.asdict(net.config), control=control, **kw)
    got["logits"] = np.asarray(ref.logits(got["state"], other))
    return got


def engine(net, **kw):
    sizes = dict(num_slots=3, page_size=PAGE, pages_per_slot=16,
                 prefix_cache=False)
    sizes.update(kw)
    return ServingEngine(net, ServingConfig(**sizes))


@pytest.fixture(scope="module")
def net():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 96, 60).astype(np.int32)


# --- the sizes -----------------------------------------------------------
def test_the_published_sizes_count_seven_billion_parameters():
    c = OlmoHybridConfig.olmo_hybrid_7b()
    assert c.layer_types.count("linear_attention") == 24
    assert c.layer_types[3::4] == ("full_attention",) * 8
    assert c.conv_width == 11520 and c.head_dim == 128
    assert round(c.layer_params(0) / 1e6, 1) == 215.6
    assert round(c.layer_params(3) / 1e6, 1) == 185.8
    assert round(c.num_params() / 1e9, 2) == 7.43
    half = OlmoHybridConfig(num_hidden_layers=16)
    assert half.layer_types == c.layer_types[:16]
    with pytest.raises(ValueError, match="layer_types"):
        OlmoHybridConfig(num_hidden_layers=2, layer_types=("sliding",) * 2)


def test_the_gates_are_drawn_as_flas_initialiser_draws_them(net):
    layers, _ = net._decode_state()
    p = layers["layer0"]
    a = np.exp(np.asarray(p["mix.A_log.weight"], np.float64))
    assert (a > 0).all() and (a < 16).all()
    dt = np.log1p(np.exp(np.asarray(p["mix.dt_bias.weight"], np.float64)))
    assert (dt > 0.9e-3).all() and (dt < 0.11).all()
    assert "attn.qkv.weight" in layers["layer2"] \
        and "mix.qkv.weight" not in layers["layer2"]


# --- the whole stack -------------------------------------------------------
def test_the_whole_stack_is_the_references(net, tokens):
    want = reference(net, tokens[:44])["logits"]
    got = np.asarray(net(tokens[:44]))
    np.testing.assert_allclose(got, want, atol=3e-4, rtol=1e-4)


@pytest.mark.parametrize("control", [c for c in ref.CONTROLS[1:]
                                     if c != "bf16_state"])
def test_every_control_moves_the_logits(net, tokens, control):
    want = reference(net, tokens[:44])["logits"]
    wrong = reference(net, tokens[:44], control, ticks=(30, 8))["logits"]
    assert np.abs(wrong - want).max() > 5e-3, control


def test_a_bf16_state_moves_the_state_more_than_the_logits(net, tokens):
    right = reference(net, tokens[:44])
    wrong = reference(net, tokens[:44], "bf16_state")
    err = max(np.linalg.norm(a - b) / np.linalg.norm(a)
              for a, b in zip(right["states"], wrong["states"]))
    assert 1e-3 < err < 0.3


# --- through the engine ----------------------------------------------------
def _against_reference(net, eng, rid, prompt, atol=3e-4):
    out = np.asarray(eng.tokens_so_far(rid))
    seq = np.concatenate([prompt, out[:-1]])
    want = reference(net, seq)["logits"][len(prompt) - 1:]
    np.testing.assert_array_equal(want.argmax(-1), out)
    np.testing.assert_allclose(
        np.asarray(eng.tick_record.top_logits(rid)), want.max(-1), atol=atol)
    return out


def test_the_engine_serves_the_references_logits_through_its_states(net,
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
    assert reg.counter("gdn/step_calls{path=xla}").value > 0
    assert reg.counter("gdn/chunk_calls{path=xla}").value > 0
    assert reg.gauge("serving/state_bytes").value == \
        eng.pool.pools.state.nbytes + eng.pool.pools.conv.nbytes


def test_the_engine_emits_the_same_tokens_on_both_prep_paths(tokens,
                                                             monkeypatch):
    """What lies between a linear layer's projections and its rule
    (``StatePools.prep``) as the ``jax.numpy`` spelling and as the Pallas
    pass (interpreted), at heads whose columns cut into whole lanes (4 of 32
    x 64): the same requests, the same tokens, and the tick counts the path
    it was traced with. The path is picked at one seam, ``ops/gdn.
    prep_path``, and that is where this test substitutes."""
    from paddle_tpu.ops import gdn

    net = build(linear_num_key_heads=4, linear_num_value_heads=4,
                linear_key_head_dim=32, linear_value_head_dim=64)
    reg = metrics.registry()
    calls = lambda path: reg.counter(                       # noqa: E731
        "gdn/prep_calls{path=%s}" % path).value

    def serve():
        eng = engine(net)
        rids = [eng.submit(tokens[:21], 12), eng.submit(tokens[30:43], 7)]
        outs = eng.run()
        assert eng.pool.check_consistency() == []
        return [outs[r].tolist() for r in rids], eng

    before = calls("xla"), calls("pallas")
    want, eng = serve()
    # four linear layers, a call for the decode rows and one for the chunk's
    assert (calls("xla"), calls("pallas")) == (before[0] + 8, before[1])
    _against_reference(net, eng, 0, tokens[:21])
    monkeypatch.setattr(gdn, "prep_path", lambda *a: "pallas")
    got, eng = serve()
    assert calls("pallas") == before[1] + 8
    assert got == want
    _against_reference(net, eng, 0, tokens[:21])


def test_a_live_slots_state_is_the_references(net, tokens):
    """What the check reads: while a request is decoding, its slot's state
    in every linear layer is the reference's after the tokens the slot
    holds."""
    eng = engine(net)
    rid = eng.submit(tokens[:21], 30)
    for _ in range(12):
        eng.step()
    eng.drain(0)
    slot, pos = eng.tick_record.stood_at(rid)
    out = np.asarray(eng.tokens_so_far(rid))
    seq = np.concatenate([tokens[:21], out])[:pos + 1]
    assert len(out) >= 5 and pos + 1 == 21 + len(out) - 1
    want = reference(net, seq)["states"]
    heads = net.config.linear_num_value_heads
    for layer, s in enumerate(want):
        got = eng.pool.pools.state_of(layer, jnp.asarray([slot + 1]),
                                      heads)[0]
        np.testing.assert_allclose(got, s, atol=2e-4, rtol=2e-3)
    # the null slot took the dead rows' writes, and no other slot's moved
    assert not np.asarray(eng.pool.pools.state[:, 2:]).any() or slot != 0


def test_two_requests_interleaved_give_what_each_gives_alone(net, tokens):
    alone = {}
    for a, n, new in ((0, 21, 12), (25, 10, 15), (40, 17, 6)):
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


def test_a_preempted_request_re_prefills_to_the_same_tokens(net, tokens):
    alone = {}
    for a, n in ((0, 14), (7, 19)):
        eng = engine(net)
        rid = eng.submit(tokens[a:a + n], 16)
        alone[(a, n)] = eng.run()[rid]
    before = metrics.registry().counter("serving/preemptions").value
    # 12 pages for two requests that need 8 and 9: one is preempted
    eng = engine(net, num_slots=2, pages_per_slot=10, num_pages=13)
    rids = {eng.submit(tokens[a:a + n], 16): (a, n) for a, n in alone}
    outs = eng.run()
    assert metrics.registry().counter("serving/preemptions").value > before
    for rid, key in rids.items():
        assert outs[rid].tolist() == alone[key].tolist()
    assert eng.pool.check_consistency() == []
    assert eng.pool.allocator.num_allocated == 0


# --- one tick, by hand -----------------------------------------------------
def test_the_ticks_statistics_and_its_dead_rows(net):
    """One tick of three decode rows (one live, one whose token has no page,
    one empty) and a chunk row at a prompt's second chunk."""
    cfg = net.config
    stacked, other = net._decode_state()
    nps, w = 8, 8
    pool = StatePagePool(net.cache_spec(), 40, PAGE, 3, nps, w)
    pool.grow_slot(0, 3)            # 12 positions: decoding at 9
    pool.grow_slot(1, 2)            # between chunks at 8: no page for 8
    pools = pool.pools._replace(
        state=pool.pools.state + 1.0, conv=pool.pools.conv + 1.0)
    tab, slots = pool.row_tables([0, 1, 2, 1])
    assert slots.tolist() == [1, 0, 3, 2]       # slot 1 rides as a chunk
    tab, slots = pool.row_tables([0, 1, 2, None])
    assert slots.tolist() == [1, 2, 3, 0]
    tok_pos = jnp.asarray([9, 8, 0] + [0] * w, jnp.int32)
    limit = jnp.asarray([32, 32, 32] + [0] * w, jnp.int32)
    tick = jax.jit(functools.partial(          # one program, as a tick is
        olmo_hybrid_ragged_apply, cfg, decode_rows=3, chunk_width=w))
    _, after, aux = tick(
        stacked, other, pools, jnp.arange(3 + w, dtype=jnp.int32),
        tok_pos, limit, (jnp.asarray(tab), slots),
        jnp.asarray([9, 8, 0, 0], jnp.int32),
        jnp.asarray([1, 1, 1, 0], jnp.int32),
        jnp.asarray([0, 1, 2], jnp.int32))
    stats = dict(zip(TICK_STATS, np.asarray(aux["stats"])))
    assert stats["live_state_rows"] == 1 and stats["chunk_tokens"] == 0
    assert stats["decode_keys"] == 10 and stats["chunk_keys"] == 0
    # slot 0 (state slot 1) moved; the slot between chunks and the empty
    # one are bit for bit what they were, state and history
    assert not np.array_equal(after.state[:, 1], pools.state[:, 1])
    for dead in (2, 3):
        np.testing.assert_array_equal(after.state[:, dead],
                                      pools.state[:, dead])
        np.testing.assert_array_equal(after.conv[:, :, dead],
                                      pools.conv[:, :, dead])


# --- a tick without a chunk (ISSUE 55; tests/chunkless_tick.py) -------------
@pytest.mark.parametrize("told", [True, False])
def test_a_tick_whose_chunk_row_is_a_pad_is_one_tick_however_it_is_told(
        net, told):
    check_a_pad_tick(net, olmo_hybrid_ragged_apply, told)


def test_a_tick_with_a_chunk_is_the_program_it_was(net):
    check_a_chunk_tick(net, olmo_hybrid_ragged_apply)


def test_no_cond_of_the_tick_takes_or_returns_a_pool(net):
    """A ``cond`` a dense stretch: one before the first layer, one between
    two layers of either kind, one after the last."""
    tick, pools = a_tick(net, olmo_hybrid_ragged_apply, chunk=False)
    assert conds_without_a_pool(tick, pools) \
        == net.config.num_hidden_layers + 1


def test_the_engine_counts_the_ticks_it_tells_have_no_chunk(net, tokens):
    """A prompt of three chunks of 8 and eleven more ticks."""
    check_the_engines_count(engine(net), tokens[:21], 12, chunks=3)


# --- the pool of a state ---------------------------------------------------
def test_the_pools_of_a_state_and_their_consistency(net):
    spec = net.cache_spec()
    assert spec["kind"] == "state" and POOL_KINDS["state"] is StatePagePool
    pool = page_pool(spec, 40, PAGE, 3, 8, 8, jnp.float32, False, False)
    assert isinstance(pool, StatePagePool)
    assert isinstance(pool.pools, StatePools)
    # two full layers of 6 heads at 8 rows; four linear layers, 3 + 1 slots
    # (the history's slot rows are whole tiles: ops/gdn.conv_slot_rows)
    assert pool.pools.kv.k.shape == (2, 40, PAGE, 8, 8)
    assert pool.pools.state.shape == (4, 4, 3, 24, 96)
    assert pool.pools.conv.shape == (4, 3, 16, 2 * 144 + 288)
    assert pool.pools.state.dtype == jnp.float32
    assert set(pool.live_shares()) == {"kv", "state"}
    assert pool.grow_slot(1, 3)
    tab, slots = pool.row_tables([0, 1, 2, 1])
    assert (tab[3, :3] > 0).all() and slots.tolist() == [1, 0, 3, 2]
    assert pool.live_shares()["state"] == pytest.approx(1 / 3)
    assert pool.check_consistency() == []
    # a state with no page behind it is an inconsistency
    pool._stateful[2] = True
    assert any("slot 2 holds a state and no page" in line
               for line in pool.check_consistency())
    pool._stateful[2] = False
    assert pool.release_slot(1) == 3 and pool.check_consistency() == []
    assert pool.live_shares()["state"] == 0
    # bf16 pools: 6 heads ride at a bf16 tile's 16 rows
    assert StatePagePool(spec, 8, PAGE, 1, 4, 8, jnp.bfloat16
                         ).pools.kv.k.shape[-2] == 16


def test_what_a_state_cannot_do_is_refused_in_its_own_words(net, tokens):
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
    for call in (lambda: eng.export_held(0), lambda: eng.admit_prefilled({}),
                 lambda: eng.export_prefix_chain(tokens[:8]),
                 lambda: eng.import_prefix_chain({})):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            call()
    assert set(StatePagePool.CANNOT) == {"prefix", "rewinds", "int8",
                                         "handoff", "chunk_rows"}
