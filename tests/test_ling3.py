"""Ling-3.0-flash at a small size (a leading dense layer and two periods of two
KDA layers and an MLA layer, 4 heads of 16 x 16 beside 4 latent heads, 16
experts in 4 groups of which a token keeps 2, pages of 4): the whole stack
and the engine (a prompt in chunks, then decode through latent pages and
state slots in one pool) against the float32 reference
``models/ling3_reference.py`` on seeded weights, logits and not tokens; the
reference's controls; the shares of an expert layer; tenants of one engine
and of one slot; the pool of a state beside latent pages and what it
refuses."""
import dataclasses

import functools
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import ling3_reference as ref
from paddle_tpu.models.ling3 import (TICK_STATS, Ling3, Ling3Config,
                                     ling3_ragged_apply)
from paddle_tpu.profiler import metrics
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.paged_cache import (POOL_KINDS, LatentPools,
                                            StatePagePool, StatePools,
                                            page_pool)

PAGE = 4


def build(seed=5, **kw):
    paddle.seed(seed)
    net = Ling3(Ling3Config.tiny(**kw))
    net.eval()
    return net


def layers_of(net, weights=None):
    layers = weights or net._decode_state()[0]
    c = net.config
    for i, kind in enumerate(c.layer_kinds):
        yield kind, c.is_moe(i), layers[f"layer{i}"]


def reference(net, tokens, control=None, weights=None, held=None, **kw):
    other = net._decode_state()[1]
    got = ref.forward(layers_of(net, weights), other, tokens,
                      dataclasses.asdict(net.config),
                      held=held or net.config.held, control=control, **kw)
    got["logits"] = np.asarray(ref.logits(got["state"], other))
    return got


def engine(net, **kw):
    sizes = dict(num_slots=3, page_size=PAGE, pages_per_slot=16,
                 prefix_cache=False)
    sizes.update(kw)
    return ServingEngine(net, ServingConfig(**sizes))


@pytest.fixture(scope="module")
def net():
    return build(experts_held=(4, 8))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 96, 60).astype(np.int32)


# --- the sizes -----------------------------------------------------------
def test_the_published_sizes_and_the_cut():
    c = Ling3Config.ling3_flash()
    kinds = c.layer_kinds
    assert kinds.count("mla") == 7 and kinds[5::6] == ("mla",) * 7
    assert not c.is_moe(1) and c.is_moe(2)
    assert round(c.mixer_params("kda") / 1e6, 1) == 63.0
    assert round(c.mixer_params("mla") / 1e6, 1) == 32.0
    assert c.conv_width == 12288 and c.softmax_scale == 192 ** -0.5
    # about 125 B parameters, 5.5 B of them a token's
    assert 120 < c.num_params() / 1e9 < 130
    cut = dataclasses.replace(
        c, num_hidden_layers=7, layer_ids=(1, 2, 3, 4, 5, 6, 7),
        vocab_size=39296, experts_held=(0, 128))
    assert cut.layer_kinds == ("kda",) * 4 + ("mla", "kda", "kda")
    assert [cut.is_moe(i) for i in range(7)] == [False] + [True] * 6
    assert round(cut.num_params() / 1e9, 2) == 5.23
    with pytest.raises(ValueError, match="layer_ids"):
        Ling3Config(num_hidden_layers=3, layer_ids=(0, 1))
    with pytest.raises(ValueError, match="n_group"):
        Ling3Config(num_experts=100)
    with pytest.raises(ValueError, match="topk_group"):
        Ling3Config(topk_group=9)


def test_the_gates_are_drawn_as_flas_initialiser_draws_them(net):
    layers, _ = net._decode_state()
    p = layers["layer0"]
    a = np.exp(np.asarray(p["mix.A_log.weight"], np.float64))
    assert a.shape == (4,) and (a >= 1).all() and (a < 16).all()
    dt = np.log1p(np.exp(np.asarray(p["mix.dt_bias.weight"], np.float64)))
    assert dt.shape == (64,) and (dt > 0.9e-3).all() and (dt < 0.11).all()
    assert "attn.q.weight" in layers["layer2"] \
        and "mix.qkv.weight" not in layers["layer2"]
    assert "ffn.fc_in.weight" in layers["layer0"] \
        and layers["layer1"]["ffn.w_gate"].shape == (8, 32, 16)
    bias = np.asarray(layers["layer1"]["ffn.select_bias"])
    assert bias.shape == (16,) and 0 < np.abs(bias).max() < 0.1


# --- the whole stack -------------------------------------------------------
def test_the_whole_stack_is_the_references(net, tokens):
    """float32 on both sides: what differs is the order of sums (the chunked
    rule against the token recurrence, the absorbed attention against the
    expanded), 1e-5 of logits of a few units."""
    want = reference(net, tokens[:44])["logits"]
    got = np.asarray(net(tokens[:44]))
    np.testing.assert_allclose(got, want, atol=3e-4, rtol=1e-4)


def test_bf16_where_float32_is_stated_fails_the_stacks_tolerance(net, tokens):
    """The tolerance above is tight enough: the same weights rounded to bf16
    move the logits a hundred times past it."""
    want = reference(net, tokens[:44])["logits"]
    layers, _ = net._decode_state()
    rounded = {n: {k: v.astype(jnp.bfloat16).astype(v.dtype)
                   for k, v in p.items()} for n, p in layers.items()}
    got = reference(net, tokens[:44], weights=rounded)["logits"]
    assert np.abs(got - want).max() > 3e-2


@pytest.mark.parametrize("control", [c for c in ref.CONTROLS[1:]
                                     if c != "bf16_state"])
def test_every_control_moves_the_logits(net, tokens, control):
    want = reference(net, tokens[:44])["logits"]
    wrong = reference(net, tokens[:44], control, ticks=(30, 8))["logits"]
    assert np.abs(wrong - want).max() > 5e-2, control


def test_a_bf16_state_moves_the_state_more_than_the_logits(net, tokens):
    right = reference(net, tokens[:44])
    wrong = reference(net, tokens[:44], "bf16_state")
    err = [np.linalg.norm(a - b) / np.linalg.norm(a)
           for a, b in zip(right["states"], wrong["states"])]
    # in the first KDA layer, whose input is the same on both sides, what
    # the rounding of the state itself does; deeper, what it did below too
    assert 1e-3 < err[0] < 0.05 and err[0] < max(err) < 1.0


# --- the share of an expert layer --------------------------------------------
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_reference(tokens):
    """The share test: the four shares' routed parts (experts 0-3, ..,
    12-15) plus the shared expert counted once are the uncut layer's, in
    the program and against the reference."""
    from paddle_tpu.distributed.moe import held_moe

    whole = build(num_hidden_layers=2, layer_ids=(1, 2))
    c = whole.config
    p = whole._decode_state()[0]["layer1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (40, c.hidden_size))
    kw = dict(scoring="sigmoid", select_bias=p["ffn.select_bias"],
              n_group=c.n_group, topk_group=c.topk_group,
              routed_scaling=c.routed_scaling_factor)
    shared = tuple(p["ffn.shared_" + k] for k in ("gate", "up", "down"))
    full, rows = held_moe(x, p["ffn.gate"], p["ffn.w_gate"], p["ffn.w_up"],
                          p["ffn.w_down"], c.num_experts_per_tok, (0, 16),
                          shared=shared, **kw)
    assert int(rows.sum()) == 40 * c.num_experts_per_tok
    parts = 0.0
    for first in range(0, 16, 4):
        cut = slice(first, first + 4)
        y, got = held_moe(x, p["ffn.gate"], p["ffn.w_gate"][cut],
                          p["ffn.w_up"][cut], p["ffn.w_down"][cut],
                          c.num_experts_per_tok, (first, 4), **kw)
        np.testing.assert_array_equal(got, rows[cut])
        parts = parts + y
    once = (jax.nn.silu(x @ shared[0]) * (x @ shared[1])) @ shared[2]
    np.testing.assert_allclose(parts + once, full, atol=2e-5, rtol=1e-5)
    # and the reference's uncut expert layer is the sum of its shares
    cfg, eps = dataclasses.asdict(c), c.rms_norm_eps
    fp = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()
          if k.startswith(("ffn.", "ln_2."))}
    uncut, _ = ref._ffn_fn(True, ref._static(cfg), (0, 16), None)(x, fp)
    y = ref.rms_norm(x, fp["ln_2.weight"], eps)
    once = (jax.nn.silu(y @ shared[0]) * (y @ shared[1])) @ shared[2]
    routed = 0.0
    for first in range(0, 16, 4):
        cut = slice(first, first + 4)
        share = dict(fp, **{k: fp[k][cut] for k in ref._KEPT})
        got, _ = ref._ffn_fn(True, ref._static(cfg), (first, 4), None)(
            x, share)
        routed = routed + (got - x - once)
    np.testing.assert_allclose(x + routed + once, uncut, atol=2e-5,
                               rtol=1e-5)


# --- through the engine ----------------------------------------------------
def _against_reference(net, eng, rid, prompt, atol=3e-4):
    out = np.asarray(eng.tokens_so_far(rid))
    seq = np.concatenate([prompt, out[:-1]])
    got = reference(net, seq)
    want = got["logits"][len(prompt) - 1:]
    np.testing.assert_array_equal(want.argmax(-1), out)
    np.testing.assert_allclose(
        np.asarray(eng.tick_record.top_logits(rid)), want.max(-1), atol=atol)
    theirs = np.stack([np.asarray(r)[len(prompt) - 1:]
                       for r in got["routed"]], 1)
    np.testing.assert_array_equal(
        np.sort(eng.tick_record.routed_experts(rid), -1),
        np.sort(theirs, -1))
    return out


def test_the_engine_serves_the_references_logits_through_its_pool(net,
                                                                  tokens):
    """A prompt in 3 chunks of 8 and then 20 decoded tokens: every emitted
    token is the reference's argmax, the tick's largest logit the
    reference's and the experts it chose the reference's; a second request
    shares the ticks."""
    reg = metrics.registry()
    eng = engine(net)
    assert eng.prefill_chunk == 8
    a = eng.submit(tokens[:21], 20)
    b = eng.submit(tokens[30:43], 9)
    eng.run()
    _against_reference(net, eng, a, tokens[:21])
    _against_reference(net, eng, b, tokens[30:43])
    assert eng.pool.check_consistency() == []
    for name in TICK_STATS[:-1]:
        assert reg.counter(
            "serving/tick_stat_sum{stat=%s}" % name).value > 0, name
    # the rows held_moe gave out are the rows the tick's own routing counts
    assert reg.counter("serving/tick_stat_sum{stat=%s}" % TICK_STATS[-1]
                       ).value == 0
    for kind in ("step", "chunk", "prep"):
        assert reg.counter("gdn/%s_calls{path=xla}" % kind).value > 0
    assert reg.counter(
        "serving/latent_attn_calls{path=xla,kind=dense}").value > 0
    assert reg.gauge("serving/state_bytes").value == \
        eng.pool.pools.state.nbytes + eng.pool.pools.conv.nbytes
    # (as the last tick left them: the one pool under its two names)
    assert 0 < reg.gauge("serving/live_pages{pool=latent}").value < 1
    assert 0 < reg.gauge("serving/live_pages{pool=state}").value <= 1


def test_a_live_slots_state_is_the_references(net, tokens):
    """What the check reads: while a request is decoding, its slot's state
    in every KDA layer is the reference's after the tokens the slot
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
    assert len(want) == 5
    for layer, s in enumerate(want):
        got = eng.pool.pools.state_of(layer, jnp.asarray([slot + 1]), 4)[0]
        np.testing.assert_allclose(got, s, atol=2e-4, rtol=2e-3)
    assert eng.pool.live_shares() == {"latent": pytest.approx(
        eng.pool.allocator.utilization()), "state": pytest.approx(1 / 3)}


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
    one empty) and a chunk row of no tokens."""
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
        ling3_ragged_apply, cfg, decode_rows=3, chunk_width=w))
    _, after, aux = tick(
        stacked, other, pools, jnp.arange(3 + w, dtype=jnp.int32),
        tok_pos, limit, (jnp.asarray(tab), slots),
        jnp.asarray([9, 8, 0, 0], jnp.int32),
        jnp.asarray([1, 1, 1, 0], jnp.int32),
        jnp.asarray([0, 1, 2], jnp.int32))
    stats = dict(zip(TICK_STATS, np.asarray(aux["stats"])))
    assert stats["live_state_rows"] == 1 and stats["chunk_tokens"] == 0
    assert stats["decode_keys"] == 10 and stats["chunk_keys"] == 0
    assert stats["decode_pairs"] == 10 and stats["chunk_pairs"] == 0
    assert stats["held_rows_unaccounted"] == 0
    assert 0 <= stats["group_hit_share"] <= 1
    assert aux["routed"].shape == (6, 3, cfg.num_experts_per_tok)
    # slot 0 (state slot 1) moved; the slot between chunks and the empty
    # one are bit for bit what they were, state and history
    assert not np.array_equal(after.state[:, 1], pools.state[:, 1])
    for dead in (2, 3):
        np.testing.assert_array_equal(after.state[:, dead],
                                      pools.state[:, dead])
        np.testing.assert_array_equal(after.conv[:, :, dead],
                                      pools.conv[:, :, dead])


# --- the pool of a state beside latent pages --------------------------------
def test_the_pool_of_a_state_beside_latent_pages_and_its_consistency(net):
    spec = net.cache_spec()
    assert spec["kind"] == "state" and spec["latent_width"] == 16
    assert "heads" not in spec and POOL_KINDS["state"] is StatePagePool
    pool = page_pool(spec, 40, PAGE, 3, 8, 8, jnp.float32, False, False)
    assert isinstance(pool, StatePagePool)
    assert isinstance(pool.pools, StatePools)
    assert isinstance(pool.pools.kv, LatentPools)
    # two MLA layers' latent rows of 8 + 8, no indexer keys, no window; five
    # KDA layers of 4 heads in pairs (16 is no whole tile), 3 + 1 slots
    assert pool.pools.kv.latent.shape == (2, 40, 16, PAGE)
    assert pool.pools.kv.index_k.size == 0 and pool.pools.kv.window.size == 0
    assert pool.pools.state.shape == (5, 4, 2, 16, 32)
    assert pool.pools.conv.shape == (5, 3, 16, 3 * 64)
    assert pool.pools.state.dtype == jnp.float32
    assert set(pool.pools.arrays()) == {"latent", "index_k", "window",
                                        "state", "conv"}
    assert set(pool.live_shares()) == {"latent", "state"}
    assert pool.grow_slot(1, 3) and pool.slot_pages(1) == 3
    assert pool.grow_slot(1, 2) and pool.slot_pages(1) == 5
    tab, slots = pool.row_tables([0, 1, 2, 1])
    assert (tab[3, :5] > 0).all() and slots.tolist() == [1, 0, 3, 2]
    assert pool.live_shares()["state"] == pytest.approx(1 / 3)
    assert pool.check_consistency() == []
    # a state with no page behind it is an inconsistency
    pool._stateful[2] = True
    assert any("slot 2 holds a state and no page" in line
               for line in pool.check_consistency())
    pool._stateful[2] = False
    assert pool.release_slot(1) == 5 and pool.check_consistency() == []
    assert pool.live_shares() == {"latent": 0.0, "state": 0.0}
    # at the published widths a head's state lies alone on whole tiles
    wide = jax.eval_shape(lambda: StatePools.zeros(
        dict(spec, state_heads=32, key_dim=128, value_dim=128), 8, 128, 2,
        jnp.bfloat16))
    assert wide.state.shape == (5, 3, 32, 128, 128)


@pytest.mark.parametrize("what,words,make", [
    ("prefix", "states at that page's boundary",
     lambda net: engine(net, prefix_cache=True)),
    ("rewinds", "roll the state back", lambda net: engine(
        net, spec=__import__("paddle_tpu.serving.spec", fromlist=["x"])
        .SpecConfig(draft_model=net, k=2))),
    ("int8", "a latent row has no head axis",
     lambda net: engine(net, kv_dtype="int8")),
    ("chunk_rows", "the second needs the state the first leaves",
     lambda net: engine(net, prefill_chunks_per_tick=2)),
    ("handoff", "not pages and nothing ships them",
     lambda net: engine(net).submit(np.arange(5, dtype=np.int32), 2,
                                    hold_after_prefill=True)),
])
def test_what_the_pool_cannot_do_is_refused_in_words_that_fit_both(
        net, what, words, make):
    assert what in StatePagePool.CANNOT
    with pytest.raises(NotImplementedError, match=words):
        make(net)
    # the sentences speak of pages, whatever the pages hold
    assert "K/V page" not in StatePagePool.CANNOT[what]


def test_the_pool_refuses_a_chunk_of_part_pages_and_every_handoff(net,
                                                                  tokens):
    with pytest.raises(ValueError, match="not whole pages"):
        engine(net, prefill_chunk=6)
    eng = engine(net)
    for call in (lambda: eng.export_held(0), lambda: eng.admit_prefilled({}),
                 lambda: eng.export_prefix_chain(tokens[:8]),
                 lambda: eng.import_prefix_chain({})):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            call()
    assert set(StatePagePool.CANNOT) == {"prefix", "rewinds", "int8",
                                         "handoff", "chunk_rows"}
