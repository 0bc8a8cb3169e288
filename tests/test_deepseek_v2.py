"""DeepSeek-V2 at a small size (16 experts in 4 groups of which a token keeps
2, a YaRN block whose original length a few dozen tokens pass, pages of 4):
the whole stack and the engine (chunked prefill two chunks a tick, then
decode through the latent cache) against the float32 reference
``models/deepseek_v2_reference.py`` on seeded weights, logits; the dense
latent kernel interpreted against the XLA walk; YaRN's table against the
closed numbers of the published block; the router's group limit; the held
shares' sum; the pools of a model with no indexer and no window; and what
is refused."""
import dataclasses

import functools
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import moe
from paddle_tpu.models import deepseek_v2_reference as ref
from paddle_tpu.models.deepseek_v2 import (TICK_STATS, YARN, DeepseekV2,
                                           DeepseekV2Config,
                                           deepseek_v2_ragged_apply,
                                           yarn_bounds, yarn_table)
from paddle_tpu.ops import latent_attention as pa
from paddle_tpu.profiler import metrics
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.paged_cache import LatentPagePool

PAGE = 4


def build(seed=3, **kw):
    paddle.seed(seed)
    cfg = DeepseekV2Config.tiny(**{"experts_held": (4, 4), **kw})
    net = DeepseekV2(cfg)
    net.eval()
    return net


def layers_of(net):
    layers, _ = net._decode_state()
    for i in range(net.config.num_hidden_layers):
        yield net.config.is_moe(i), layers[f"layer{i}"]


def reference(net, tokens, control=None, held=None):
    cfg = net.config
    got = ref.forward(layers_of(net), net._decode_state()[1], tokens,
                      dataclasses.asdict(cfg), held or cfg.held, control)
    got["logits"] = np.asarray(ref.logits(got["state"],
                                          net._decode_state()[1]))
    return got


def engine(net, **kw):
    sizes = dict(num_slots=3, page_size=PAGE, pages_per_slot=16,
                 prefix_cache=False, prefill_chunks_per_tick=2)
    sizes.update(kw)
    return ServingEngine(net, ServingConfig(**sizes))


@pytest.fixture(scope="module")
def net():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 96, 44).astype(np.int32)


# --- YaRN ------------------------------------------------------------------
def test_yarns_table_is_the_closed_numbers_of_the_published_block():
    c = DeepseekV2Config()
    assert yarn_bounds(64, 1e4, YARN) == (10, 23)
    inv, cos_sin, factor = yarn_table(64, 1e4, YARN)
    assert inv.dtype == np.float32 and inv.shape == (32,)
    f = 1e4 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-6)   # unscaled
    np.testing.assert_allclose(inv[23:], f[23:] / 40, rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    ramp = (16 - 10) / 13
    np.testing.assert_allclose(inv[16], f[16] * (1 - ramp) + f[16] / 40
                               * ramp, rtol=1e-6)
    assert cos_sin == 1.0
    m = 0.1 * 0.707 * np.log(40) + 1
    assert round(m, 4) == 1.2608 and factor == pytest.approx(m * m)
    assert round(c.softmax_scale, 5) == 0.11472
    # without a scaling block: the plain table, no factor
    plain, one, unit = yarn_table(64, 1e4, None)
    np.testing.assert_allclose(plain, f, rtol=1e-6)
    assert (one, unit) == (1.0, 1.0)
    assert dataclasses.replace(c, rope_scaling=None).softmax_scale == \
        pytest.approx(192 ** -0.5)
    # the reference computes its own, and they agree
    r_inv, r_cs, r_scale = ref.yarn(dataclasses.asdict(c))
    np.testing.assert_allclose(r_inv, inv, rtol=1e-6)
    assert r_cs == 1.0 and r_scale == pytest.approx(c.softmax_scale)
    with pytest.raises(NotImplementedError, match="linear"):
        yarn_table(64, 1e4, {"type": "linear", "factor": 2})


def test_the_published_sizes_count_236_billion_parameters():
    c = DeepseekV2Config.deepseek_v2()
    assert round(c.num_params() / 1e9, 1) == 235.7
    assert round(c.attention_params() / 1e6, 1) == 149.2
    cut = dataclasses.replace(c, num_hidden_layers=5, vocab_size=12800,
                              experts_held=(0, 20))
    assert round(cut.num_params() / 1e9, 3) == 3.145
    with pytest.raises(ValueError, match="n_group"):
        DeepseekV2Config(n_routed_experts=100)
    with pytest.raises(ValueError, match="topk_group"):
        DeepseekV2Config(topk_group=9)


# --- the router -------------------------------------------------------------
def _route_rows(x, w, top_k, **kw):
    _, _, experts, gates, _, _ = moe._route(x, w, top_k, **kw)
    return np.stack([np.asarray(e) for e in experts], 1), \
        np.stack([np.asarray(g) for g in gates], 1)


def test_a_token_never_leaves_its_kept_groups():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 24)), jnp.float32)
    experts, gates = _route_rows(x, w, 5, n_group=6, topk_group=2)
    probs = np.asarray(jax.nn.softmax(x @ w, -1))
    best = probs.reshape(64, 6, 4).max(-1)
    kept = np.argsort(-best, -1)[:, :2]
    for t in range(64):
        assert set(experts[t] // 4) <= set(kept[t]), t
        # within them: the five largest scores, in order, as weights
        inside = np.where(np.isin(np.arange(24) // 4, kept[t]), probs[t], 0)
        want = np.argsort(-inside)[:5]
        assert list(experts[t]) == list(want)
        np.testing.assert_allclose(gates[t], probs[t][want], rtol=1e-5)
    # some token's plain top-5 does leave its two groups: the limit binds
    plain, _ = _route_rows(x, w, 5)
    assert (plain != experts).any()


def test_one_group_is_plain_top_k_and_the_defaults_change_no_program():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 12)), jnp.float32)
    e1, g1 = _route_rows(x, w, 3)
    e2, g2 = _route_rows(x, w, 3, n_group=1, topk_group=1)
    np.testing.assert_array_equal(e1, e2)
    np.testing.assert_array_equal(g1, g2)
    probs = np.asarray(jax.nn.softmax(x @ w, -1))
    np.testing.assert_array_equal(e1, np.argsort(-probs, -1)[:, :3])
    # every group kept is no limit either
    e3, _ = _route_rows(x, w, 3, n_group=4, topk_group=4)
    np.testing.assert_array_equal(e1, e3)
    # (the group limit over sigmoid scores, a group's score the sum of its
    # two best: tests/test_moe.py, since ISSUE 49)
    # held_moe with the new arguments at their defaults lowers to the text
    # it lowered to without them (OLMoE's, Solar's and dots3's callers)
    wg = jnp.asarray(rng.normal(size=(4, 16, 8)), jnp.float32)
    wd = jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)
    def text(**kw):
        return jax.jit(lambda x_: moe.held_moe(
            x_, w, wg, wg, wd, 3, (4, 4), **kw)).lower(x).as_text()

    for scoring in ("sigmoid", "softmax"):
        assert text(scoring=scoring) == text(
            scoring=scoring, n_group=1, topk_group=1, routed_scaling=1.0)
        assert text(scoring=scoring) != text(scoring=scoring,
                                             routed_scaling=16.0)


def test_routed_scaling_weighs_the_routed_experts_and_not_the_shared():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(8, 16, 8)) * .2, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(8, 8, 16)) * .2, jnp.float32)
    shared = (wg[0], wg[1], wd[0])
    args = (x, w, wg, wg, wd, 2, (0, 8))
    routed, _ = moe.held_moe(*args, scoring="softmax")
    both, _ = moe.held_moe(*args, scoring="softmax", shared=shared)
    scaled, _ = moe.held_moe(*args, scoring="softmax", shared=shared,
                             routed_scaling=4.0)
    np.testing.assert_allclose(np.asarray(scaled),
                               4 * np.asarray(routed)
                               + np.asarray(both - routed), atol=1e-5)


# --- the share ties to the model --------------------------------------------
def test_the_groups_held_parts_add_up_to_the_uncut_layer():
    """Four chips, a router group each, the shared experts counted once:
    their expert layers' outputs add up to the layer that holds all 16, in
    the program and in the reference."""
    whole = build(experts_held=None)
    cfg = whole.config
    layers, _ = whole._decode_state()
    p = layers["layer1"]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(24, cfg.hidden_size)), jnp.float32)
    shared = tuple(p["ffn.shared_" + k] for k in ("gate", "up", "down"))
    kw = dict(scoring="softmax", n_group=cfg.n_group,
              topk_group=cfg.topk_group,
              routed_scaling=cfg.routed_scaling_factor)

    def part(first, count, shared_=None):
        sl = slice(first, first + count)
        y, rows = moe.held_moe(
            x, p["ffn.gate"], p["ffn.w_gate"][sl], p["ffn.w_up"][sl],
            p["ffn.w_down"][sl], cfg.num_experts_per_tok, (first, count),
            shared=shared_, **kw)
        return np.asarray(y), np.asarray(rows)

    full, rows_all = part(0, 16, shared)
    shares = [part(4 * g, 4) for g in range(4)]
    once = part(0, 4, shared)[0] - shares[0][0]        # the shared experts
    np.testing.assert_allclose(sum(y for y, _ in shares) + once, full,
                               atol=2e-5)
    assert sum(int(r.sum()) for _, r in shares) == int(rows_all.sum()) \
        == 24 * cfg.num_experts_per_tok
    # the reference's layer, uncut, says the same of the same input
    ffn = ref._ffn_fn(True, ref._static(dataclasses.asdict(cfg)), (0, 16),
                      None)
    weights = {k: v for k, v in p.items()
               if k.startswith(("ffn.", "ln_2."))}
    weights["ln_2.weight"] = jnp.ones_like(weights["ln_2.weight"])
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                               + cfg.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        theirs, _ = ffn(x, weights)
        mine_full, _ = moe.held_moe(
            normed, p["ffn.gate"], p["ffn.w_gate"], p["ffn.w_up"],
            p["ffn.w_down"], cfg.num_experts_per_tok, (0, 16),
            shared=shared, **kw)
    np.testing.assert_allclose(np.asarray(theirs) - np.asarray(x),
                               np.asarray(mine_full), atol=2e-4)


# --- the stack and the engine against the reference --------------------------
def test_the_whole_stack_is_the_references(net, tokens):
    got = np.asarray(net(tokens))
    want = reference(net, tokens)["logits"]
    assert got.shape == (44, 96)
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert np.abs(want).max() > 1.0


@pytest.mark.parametrize("control", ref.CONTROLS[1:])
def test_every_control_moves_the_logits(net, tokens, control):
    want = reference(net, tokens)["logits"]
    wrong = reference(net, tokens, control)["logits"]
    assert np.abs(wrong - want).max() > 0.1, control


def test_the_engine_serves_the_references_logits_two_chunks_a_tick(net,
                                                                    tokens):
    """Prefill in chunks of 8, two a tick, then decode through the latent
    cache: every emitted token is the reference's argmax at its position and
    the tick's largest logit is the reference's, for two requests that
    share the ticks."""
    reg = metrics.registry()
    before = reg.counter(
        "serving/latent_attn_calls{path=xla,kind=dense}").value
    eng = engine(net)
    assert eng.prefill_chunk == 8
    a = eng.submit(tokens[:29], 9)
    b = eng.submit(tokens[5:19], 12)
    eng.run()
    assert reg.counter(
        "serving/latent_attn_calls{path=xla,kind=dense}").value > before
    assert reg.counter("serving/prefill_chunks").value >= 6
    for rid, prompt in ((a, tokens[:29]), (b, tokens[5:19])):
        out = np.asarray(eng.tokens_so_far(rid))
        seq = np.concatenate([prompt, out[:-1]])
        want = reference(net, seq)["logits"][len(prompt) - 1:]
        np.testing.assert_array_equal(want.argmax(-1), out)
        np.testing.assert_allclose(
            np.asarray(eng.tick_record.top_logits(rid)), want.max(-1),
            atol=2e-4)
        # the experts the ticks say each emitting row chose are the
        # reference's at the same positions
        routed = eng.tick_record.routed_experts(rid)
        theirs = reference(net, seq)["routed"]
        assert routed.shape == (len(out), 2, 3)
        for layer in range(2):
            np.testing.assert_array_equal(
                np.sort(routed[:, layer], -1),
                np.sort(np.asarray(theirs[layer])[len(prompt) - 1:], -1))
        assert eng.tick_record.selected_sets(rid)[0][1] == []
    # two chunks a tick: some tick carried two prefill rows
    assert eng.pool.check_consistency() == []
    for name in TICK_STATS:
        assert reg.counter(
            "serving/tick_stat_sum{stat=%s}" % name).value > 0, name


def test_the_ticks_statistics_count_what_the_tick_held(net):
    """One tick of two decode rows and two chunk rows: the group hits, the
    held experts' rows and the attention's pairs and keys, by hand."""
    from paddle_tpu.serving.paged_cache import LatentPools

    cfg = net.config
    stacked, other = net._decode_state()
    ps, nps, w = PAGE, 8, 4
    pools = LatentPools.zeros(3, 4 * nps + 1, 0, 2, ps, 16, 0, 0,
                              jnp.float32)
    tab = jnp.asarray(np.arange(1, 4 * nps + 1).reshape(4, nps), jnp.int32)
    tab = tab.at[1].set(0)                       # a free slot's decode row
    # rows: decode at position 9, a free slot, chunks at 4.. and 8..(3 real)
    tok_pos = jnp.asarray([9, 0, 4, 5, 6, 7, 8, 9, 10, 11], jnp.int32)
    row_pos0 = jnp.asarray([9, 0, 4, 8], jnp.int32)
    row_len = jnp.asarray([1, 0, 4, 3], jnp.int32)
    limit = jnp.asarray([10, 0, 8, 8, 8, 8, 11, 11, 11, 11], jnp.int32)
    toks = jnp.arange(10, dtype=jnp.int32)
    tick = jax.jit(functools.partial(          # one program, as a tick is
        deepseek_v2_ragged_apply, cfg, decode_rows=2, chunk_width=w))
    _, _, aux = tick(
        stacked, other, pools, toks, tok_pos, limit,
        (tab, jnp.zeros_like(tab)), row_pos0, row_len,
        jnp.asarray([0, 1], jnp.int32))
    stats = dict(zip(TICK_STATS, np.asarray(aux["stats"])))
    assert stats["decode_pairs"] == 10 and stats["decode_keys"] == 10
    assert stats["chunk_pairs"] == (5 + 6 + 7 + 8) + (9 + 10 + 11)
    assert stats["chunk_keys"] == 8 + 11
    assert 0 <= stats["group_hit_share"] <= 1
    assert stats["expert_rows"] <= 8 * cfg.num_experts_per_tok
    assert aux["routed"].shape == (2, 2, 3)
    assert aux["selected"].shape[0] == 0 and aux["window_lse"].shape[0] == 0


# --- the pools of a model with no indexer and no window ----------------------
def test_latent_pools_without_indexer_or_window_allocate_nothing_for_them(
        net):
    spec = net.cache_spec()
    assert "index_width" not in spec and "window_layers" not in spec
    pool = LatentPagePool(spec, 40, PAGE, 3, 8, 8)
    assert pool.pools.latent.shape == (3, 40, 16, PAGE)
    assert pool.pools.index_k.size == 0 and pool.pools.window.size == 0
    assert pool.window_pages_per_slot == 0
    assert pool.grow_slot(1, 3) and pool.slot_pages(1) == 3
    assert pool.window_allocator.num_allocated == 0
    assert pool.free_behind(1, 100) == 0
    assert set(pool.live_shares()) == {"latent"}
    full, window = pool.row_tables([1])
    assert (np.asarray(full)[0, :3] > 0).all() and not np.asarray(window).any()
    assert pool.release_slot(1) == 3 and pool.check_consistency() == []


def test_a_prefix_cache_over_latent_pools_is_refused_for_what_is_missing(
        net):
    with pytest.raises(NotImplementedError,
                       match="share_into_slot.*copy_page"):
        engine(net, prefix_cache=True)
    # a model with windowed layers is still told of them
    from paddle_tpu.models.dots3 import Dots3, Dots3Config

    with pytest.raises(NotImplementedError, match="windowed layers"):
        LatentPagePool(Dots3(Dots3Config.tiny()).cache_spec(), 40, PAGE, 3,
                       8, 8, prefix_cache=True)
    with pytest.raises(NotImplementedError, match="int8 latent pools"):
        engine(net, kv_dtype="int8")


# --- the dense latent kernel, interpreted ------------------------------------
NH, W, C, NPS = 4, 24, 16, 10
CAP = NPS * PAGE


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of four pages of 4 and tiles of 8 queries x 4 heads, so that
    toy rows cross blocks and tiles."""
    monkeypatch.setattr(pa, "_LATENT_BLOCK_TOKENS", 16)
    monkeypatch.setattr(pa, "_LATENT_TILE_ROWS", 8 * NH)


def _case(pos0, true_len, t, dtype, seed=0):
    rng = np.random.RandomState(seed)
    pos0 = np.asarray(pos0, np.int32)
    true_len = np.asarray(true_len, np.int32)
    r = len(pos0)
    pages = r * NPS + 1
    table = rng.permutation(np.arange(1, pages)).reshape(r, NPS)
    table[true_len == 0] = 0
    pool = jnp.asarray(rng.randn(2, pages, W, PAGE), dtype)
    q = jnp.asarray(rng.randn(r, t, NH, W), dtype)
    meta = (jnp.asarray(table.astype(np.int32)), jnp.asarray(pos0),
            jnp.asarray(true_len))
    return q, pool, meta


def _softmax(q, pool, meta, layer=1):
    table, pos0, true_len = (np.asarray(m) for m in meta)
    r, t = q.shape[:2]
    flat = np.swapaxes(np.asarray(pool, np.float32)[layer][table], 2, 3)
    flat = flat.reshape(r, CAP, W)
    live = np.where(true_len > 0, np.minimum(pos0 + true_len, CAP), 0)
    last = np.minimum(pos0[:, None] + np.arange(t)[None], live[:, None] - 1)
    seen = np.arange(CAP)[None, None, :] <= last[:, :, None]
    s = np.einsum("rtnc,rsc->rtns", np.asarray(q, np.float32), flat) * 0.3
    s = np.where(seen[:, :, None, :], s, -np.inf)
    with np.errstate(all="ignore"):
        p = np.exp(s - s.max(-1, keepdims=True))
        p = np.nan_to_num(p / p.sum(-1, keepdims=True))
    return np.einsum("rtns,rsc->rtnc", p, flat[..., :C]), \
        np.arange(t)[None, :] < true_len[:, None]


def _attend(impl, q, pool, meta, layer=1):
    f = jax.jit(lambda q_, pool_, ly: pa.latent_attention(
        q_, pool_, ly, *meta, C, 0.3, impl=impl))
    return np.asarray(f(q, pool, jnp.int32(layer)), np.float32)


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 2e-5)])
@pytest.mark.parametrize("pos0,true_len,t", [
    ((0, 13, 24, 0, 7), (16, 16, 9, 0, 3), 16),     # chunks, a free slot,
                                                    # pad tiles, block edges
    ((15, 16, 17, 0, 39, 3), (1, 1, 1, 0, 1, 1), 1),    # decode rows
    ((0,), (40,), 40),                              # a whole slot at once
])
def test_the_dense_kernel_is_the_walk_and_the_softmax(small_blocks, dtype,
                                                      tol, pos0, true_len,
                                                      t):
    q, pool, meta = _case(pos0, true_len, t, dtype)
    want, real = _softmax(q, pool, meta)
    walk = _attend("xla", q, pool, meta)
    kernel = _attend("pallas", q, pool, meta)
    scale = np.abs(want[real]).max()
    assert np.abs(walk - want)[real].max() <= tol * scale
    assert np.abs(kernel - want)[real].max() <= tol * scale
    assert np.abs(kernel - walk)[real].max() <= tol * scale
    # a free slot's row and a row with nothing live get zeros from the kernel
    dead = np.asarray(true_len) == 0
    assert not kernel[dead].any()


def test_the_dense_path_is_picked_and_counted_where_traced(
        small_blocks, attention_spelling):
    q, pool, meta = _case((0, 5), (8, 3), 8, jnp.float32)
    reg = metrics.registry()
    names = {p: "serving/latent_attn_calls{path=%s,kind=dense}" % p
             for p in ("xla", "pallas")}
    before = {p: reg.counter(n).value for p, n in names.items()}
    _attend(None, q, pool, meta)         # the CPU: the walk
    _attend("pallas", q, pool, meta)
    assert reg.counter(names["xla"]).value == before["xla"] + 1
    assert reg.counter(names["pallas"]).value == before["pallas"] + 1
    with pytest.raises(ValueError, match="unknown latent attention impl"):
        _attend("mosaic", q, pool, meta)
    # an engine whose ticks are traced with the kernel picked runs it in
    # every tick, interpreted
    net = build()
    plain = engine(net)
    rid2 = plain.submit(np.arange(11, dtype=np.int32), 4)
    plain.run()
    attention_spelling("pallas")
    eng = engine(net)
    rid = eng.submit(np.arange(11, dtype=np.int32), 4)
    eng.run()
    assert list(eng.tokens_so_far(rid)) == list(plain.tokens_so_far(rid2))
    np.testing.assert_allclose(eng.tick_record.top_logits(rid),
                               plain.tick_record.top_logits(rid2), atol=1e-4)
    assert reg.counter(names["pallas"]).value > before["pallas"] + 1
