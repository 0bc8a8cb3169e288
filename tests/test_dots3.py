"""dots3-note-prev's language model at a small size (window 5, top-k 8,
page 4: every boundary is crossed within a few dozen tokens): each layer
kind and the whole stack against the float32 reference
``models/dots3_reference.py`` on seeded weights; chunked prefill and decode
through the latent, indexer-key and windowed pools of ``ServingEngine``;
the pages behind the window; the selections; the held shares' sum; the
sliced vocabulary; preemption; and what is refused."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.moe import held_moe, held_window_rows
from paddle_tpu.models import dots3_reference as ref
from paddle_tpu.models.dots3 import (FULL, SLIDING, Dots3, Dots3Config,
                                     dots3_ragged_apply)
from paddle_tpu.ops import grouped_matmul as gmm
from paddle_tpu.ops import latent_attention as pa
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.paged_cache import LatentPagePool, LatentPools

WINDOW, TOPK, PAGE = 5, 8, 4


def build(seed=3, **kw):
    paddle.seed(seed)
    cfg = Dots3Config.tiny(experts_held=(0, 4), **kw)
    net = Dots3(cfg)
    net.eval()
    return net


def layers_of(net):
    layers, _ = net._decode_state()
    for i, kind in enumerate(net.config.layer_types):
        yield kind, net.config.is_moe(i), layers[f"layer{i}"]


def reference(net, tokens, control=None, held=None):
    cfg = net.config
    got = ref.forward(layers_of(net), net._decode_state()[1], tokens,
                      dataclasses.asdict(cfg), held or cfg.held, control)
    got["logits"] = np.asarray(ref.logits(got["state"],
                                          net._decode_state()[1]))
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
    return np.random.default_rng(0).integers(0, 96, 48).astype(np.int32)


# --- the layers against the reference ------------------------------------
KINDS = {"full+dense": dict(layer_types=(FULL,), first_k_dense_replace=1),
         "full+experts": dict(layer_types=(FULL,), first_k_dense_replace=0),
         "sliding+experts": dict(layer_types=(SLIDING,),
                                 first_k_dense_replace=0),
         "sliding+dense": dict(layer_types=(SLIDING,),
                               first_k_dense_replace=1)}


@pytest.mark.parametrize("kind", list(KINDS))
def test_one_layer_of_each_kind_is_the_references(kind, tokens):
    one = build(num_hidden_layers=1, **KINDS[kind])
    mine = np.asarray(one(tokens))
    theirs = reference(one, tokens)["logits"]
    assert np.abs(theirs).max() > 0.5
    np.testing.assert_allclose(mine, theirs, atol=2e-5)


def test_the_whole_stack_is_the_references(net, tokens):
    assert net.config.layer_types == (FULL, FULL) + (SLIDING,) * 3
    assert [net.config.is_moe(i) for i in range(5)] == [False] + [True] * 4
    np.testing.assert_allclose(np.asarray(net(tokens)),
                               reference(net, tokens)["logits"], atol=5e-5)


@pytest.mark.parametrize("control", [c for c in ref.CONTROLS if c])
def test_every_control_of_the_reference_is_another_model(net, tokens,
                                                         control):
    right = reference(net, tokens)["logits"]
    wrong = reference(net, tokens, control)["logits"]
    assert np.abs(wrong - right).max() > 1e-2, control


def test_the_parameter_count_is_the_models(net):
    counted = sum(int(np.prod(p.shape)) for _, p in net.named_parameters())
    assert net.config.num_params() == counted
    full = Dots3Config(vocab_size=19008, num_hidden_layers=5,
                       layer_types=(FULL, FULL) + (SLIDING,) * 3,
                       experts_held=(0, 32))
    # ISSUE 37's arithmetic: 4.087 B parameters at the cut
    assert round(full.num_params() / 1e9, 3) == 4.087
    assert [round(full.layer_params(i) / 1e5) for i in range(3)] == [
        3564, 9239, 8707]                   # 356.4 M, 923.9 M, 870.7 M
    assert Dots3Config.dots3_note_prev().layer_types[-5:] == (
        FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert Dots3Config.dots3_note_prev().layer_types.count(FULL) == 13


# --- prefill in chunks, then decode, through the pools --------------------
#: prompts on both sides of the window (5), of top-k (8), of a page (4) and
#: of a chunk (8), and one several chunks long
PROMPTS = (2, 5, 7, 9, 23, 31)


@pytest.mark.parametrize("plen", PROMPTS)
def test_chunked_prefill_and_decode_emit_the_references_logits(net, tokens,
                                                               plen):
    eng = engine(net)
    rid = eng.submit(tokens[:plen], 12)
    out = eng.run()[rid]
    seq = np.concatenate([tokens[:plen], out[:-1]])
    logits = reference(net, seq)["logits"][plen - 1:]
    assert out.tolist() == logits.argmax(-1).tolist()
    np.testing.assert_allclose(eng.tick_record.top_logits(rid),
                               logits.max(-1),
                               atol=5e-5)
    assert eng.pool.check_consistency() == []
    assert eng.pool.window_allocator.num_allocated == 0


def test_requests_side_by_side_do_not_touch_each_other(net, tokens):
    eng = engine(net)
    rids = [eng.submit(tokens[a:a + n], 9)
            for a, n in ((0, 17), (5, 3), (11, 26), (2, 8))]
    outs = eng.run()
    for rid, (a, n) in zip(rids, ((0, 17), (5, 3), (11, 26), (2, 8))):
        seq = np.concatenate([tokens[a:a + n], outs[rid][:-1]])
        want = reference(net, seq)["logits"][n - 1:].argmax(-1)
        assert outs[rid].tolist() == want.tolist()
    assert eng.compiled_sites == (eng._tick_site,)


# --- the window's pages ----------------------------------------------------
def test_pages_behind_the_window_go_back_and_a_slot_stays_bounded(net,
                                                                  tokens):
    eng = engine(net)
    pool = eng.pool
    assert isinstance(pool, LatentPagePool)
    # ceil((5 - 1 + 8) / 4) + 2 pages a slot, whatever the slot's length
    assert pool.window_pages_per_slot == 5
    assert pool.pools.window.shape[1] == 3 * 5 + 1
    eng.submit(tokens[:30], 20)
    most, freed = 0, 0
    from paddle_tpu.profiler import registry

    before = registry().counter("serving/window_pages_freed").value
    while eng.step() or not eng.idle():
        eng.drain(0)
        held = pool.slot_window_pages(0) + pool.slot_window_pages(1) \
            + pool.slot_window_pages(2)
        most = max(most, held)
        assert pool.check_consistency() == []
    freed = registry().counter("serving/window_pages_freed").value - before
    assert most <= pool.window_pages_per_slot
    # 49 positions: 13 pages grown, all but the window's given back early
    assert freed >= 13 - 3
    assert pool.allocator.num_allocated == 0


def test_a_run_that_frees_nothing_emits_the_same_logits(net, tokens,
                                                        monkeypatch):
    def run():
        eng = engine(net)
        rid = eng.submit(tokens[:27], 14)
        return eng.run()[rid], eng.tick_record.top_logits(rid), eng

    out, tops, eng = run()
    monkeypatch.setattr(LatentPagePool, "FREE_BEHIND", False)
    out_kept, tops_kept, kept = run()
    assert kept.pool.window_pages_per_slot == 16
    assert kept.pool.pools.window.shape[1] > eng.pool.pools.window.shape[1]
    assert out.tolist() == out_kept.tolist() and tops == tops_kept


def test_free_behind_gives_back_only_what_no_query_can_see():
    spec = dict(full_layers=1, latent_width=4, index_width=4,
                window_layers=1, window_width=4, window=5)
    pool = LatentPagePool(spec, 17, PAGE, 2, 8, chunk=8)
    assert pool.grow_slot(0, 4)                  # positions 0..15
    assert pool.free_behind(0, 8) == 1           # a query at 8 sees 4..8
    assert pool.free_behind(0, 8) == 0
    assert pool.window_tables[0, :4].tolist()[0] == 0
    assert pool.free_behind(0, 9) == 0           # 5..9: page 1 still seen
    assert pool.free_behind(0, 13) == 1          # 9..13: pages 0, 1 gone
    assert pool.slot_pages(0) == 4 and pool.slot_window_pages(0) == 2
    assert pool.check_consistency() == []
    assert pool.release_slot(0) == 4
    assert pool.window_allocator.num_allocated == 0
    assert set(pool.live_shares()) == {"latent", "window"}


# --- the selection ---------------------------------------------------------
def test_selected_sets_are_the_references(net, tokens):
    eng = engine(net)
    rid = eng.submit(tokens[:29], 10)
    out = eng.run()[rid]
    seq = np.concatenate([tokens[:29], out[:-1]])
    theirs = reference(net, seq)["selected"]
    sets = eng.tick_record.selected_sets(rid)
    assert [pos for pos, _ in sets] == [28, 37]
    for pos, mine in sets:
        assert len(mine) == 2
        for layer in range(2):
            a, b = set(mine[layer].tolist()), \
                set(np.asarray(theirs[layer][pos]).tolist())
            assert len(a) == TOPK and len(a ^ b) <= 2, (pos, layer, a, b)


def test_fewer_visible_than_topk_selects_them_all(net, tokens):
    eng = engine(net)
    rid = eng.submit(tokens[:3], 2)
    eng.run()
    (pos, first), (_, last) = eng.tick_record.selected_sets(rid)
    assert pos == 2
    assert first[0].tolist() == [0, 1, 2]
    assert last[1].tolist() == [0, 1, 2, 3]


def test_the_ticks_report_themselves(net, tokens):
    from paddle_tpu.profiler import registry

    reg = registry()
    told = reg.counter("serving/tick_stat_ticks").value
    eng = engine(net)
    eng.submit(tokens[:20], 6)
    eng.run()
    assert reg.counter("serving/tick_stat_ticks").value - told == 6
    share = reg.gauge("serving/tick_stat{stat=selected_share}").value
    assert 0.25 < share < 0.45                    # 8 of ~25 visible
    assert reg.gauge("serving/tick_stat{stat=expert_rows}").value > 0
    assert 0 < reg.gauge(
        "serving/tick_stat{stat=experts_touched_share}").value <= 1
    assert 0 < reg.gauge("serving/live_pages{pool=window}").value <= 1


def test_only_the_requests_a_caller_watches_are_recorded(net, tokens):
    from paddle_tpu.profiler import registry

    told = registry().counter("serving/tick_stat_ticks").value
    eng = engine(net)
    a, b = eng.submit(tokens[:20], 3), eng.submit(tokens[:9], 4)
    eng.tick_record.watch = lambda rid: rid == b
    out = eng.run()
    rec = eng.tick_record
    assert not rec.has(a) and rec.has(b)
    assert len(rec.top_logits(b)) == len(out[b]) == 4
    assert rec.routed_experts(b).shape == (4, 4, 4)   # tokens, layers, k
    assert rec.window_lse(b).shape == (4, 3)
    # every drained tick's stats are counted, watched or not
    assert registry().counter("serving/tick_stat_ticks").value - told >= 4
    eng.reset_results()
    assert not rec.has(b)


@pytest.mark.parametrize("deviation,touched", [(0.1, (0.3, 0.75)),
                                               (0.02, (0.9, 1.0))])
def test_a_small_selection_bias_leaves_the_choice_to_the_token(deviation,
                                                               touched):
    """The 8 largest of 256 sigmoid scores sit where the sigmoid is flat,
    0.005 apart: a bias of deviation 0.1 picks the experts itself, whatever
    the token (half a held share without a row), one of 0.02 does not."""
    from paddle_tpu.distributed.moe import held_moe

    rng = np.random.default_rng(3)
    h, e, f = 64, 256, 8
    x = jnp.asarray(rng.normal(size=(262, h)), jnp.float32)
    gate = jnp.asarray(rng.normal(0, 1.43 / np.sqrt(h), (h, e)), jnp.float32)
    w = [jnp.asarray(rng.normal(0, .02, shape), jnp.float32)
         for shape in ((32, h, f), (32, h, f), (32, f, h))]
    bias = jnp.asarray(rng.normal(0, deviation, e), jnp.float32)
    _, rows = held_moe(x, gate, *w, 8, (0, 32), scoring="sigmoid",
                       select_bias=bias)
    share = float(np.mean(np.asarray(rows) > 0))
    assert touched[0] <= share <= touched[1], share


# --- the ops, each against a plain spelling -------------------------------
def _pool_with(rows, pages, width, rng):
    """A pool whose pages ``pages`` hold ``rows`` [n, width] in order."""
    pool = jnp.asarray(rng.normal(size=(1, 12, width, PAGE)), jnp.float32)
    n = rows.shape[0]
    page = np.asarray(pages)[np.arange(n) // PAGE]
    return pa.latent_scatter(pool, page, np.arange(n) % PAGE, rows, 0)


def test_index_scores_are_the_plain_sums():
    rng = np.random.default_rng(1)
    n, j, d = 14, 3, 4
    keys = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    pages = [5, 2, 9, 7]
    pool = _pool_with(keys, pages, d, rng)
    q = jnp.asarray(rng.normal(size=(1, 6, j, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(1, 6, j)), jnp.float32)
    table = np.zeros((1, 6), np.int32)
    table[0, :4] = pages
    got = np.asarray(pa.index_scores(q, w, pool, 0, table,
                                     np.asarray([8]), np.asarray([6])))[0]
    want = np.einsum("tj,tjs->ts", w[0], np.maximum(
        np.einsum("tjd,sd->tjs", q[0], keys), 0))
    for t in range(6):
        np.testing.assert_allclose(got[t, :9 + t], want[t, :9 + t],
                                   rtol=1e-5, atol=1e-5)
        assert np.all(np.isneginf(got[t, 9 + t:]))
    idx, valid = pa.select_topk(jnp.asarray(got), 12)
    assert valid.sum(-1).tolist() == [9, 10, 11, 12, 12, 12]
    assert set(np.asarray(idx)[0][np.asarray(valid)[0]]) == set(range(9))


def test_absorbed_attention_over_a_selection_is_the_expanded():
    """Two rows of one query and one row of three, each query over the 4
    best-scored of the positions it may see: the absorbed product over the
    walked pages under the selection's mask is the expanded attention over
    the selected positions alone."""
    rng = np.random.default_rng(2)
    n, nh, c, r, v, k = 13, 3, 6, 2, 5, 4
    lat = jnp.asarray(rng.normal(size=(n, c + r)), jnp.float32)
    pages = [3, 8, 1, 6]
    pool = _pool_with(lat, pages, c + r, rng)
    # junk in a page no row holds and past the rows' live positions
    pool = pool.at[0, 11].set(jnp.nan)
    w_k = rng.normal(size=(c, nh, 4)).astype(np.float32)
    w_v = rng.normal(size=(c, nh, v)).astype(np.float32)
    table = np.zeros((3, 5), np.int32)
    table[:, :4] = pages
    for pos0, t in (([12, 6, 2], 1), ([8], 3)):
        rows = len(pos0)
        pos0 = np.asarray(pos0)
        q_nope = rng.normal(size=(rows, t, nh, 4)).astype(np.float32)
        q_rope = rng.normal(size=(rows, t, nh, r)).astype(np.float32)
        score = rng.normal(size=(rows, t, 20)).astype(np.float32)
        qpos = pos0[:, None] + np.arange(t)[None]
        score = np.where(np.arange(20)[None, None] <= qpos[..., None],
                         score, -np.inf)
        keys, thr, ties = pa.select_threshold(
            jnp.asarray(score.reshape(-1, 20)), k)
        idx, valid = pa.select_topk(jnp.asarray(score.reshape(-1, 20)), k)
        q = np.concatenate([np.einsum("rtnd,cnd->rtnc", q_nope, w_k),
                            q_rope], -1)
        o_lat = pa.selected_latent_attention(
            jnp.asarray(q), pool, 0, table[:rows], pos0,
            np.full(rows, t), keys.reshape(rows, t, 20),
            thr.reshape(rows, t), ties.reshape(rows, t), c, 0.4)
        got = np.einsum("rtnc,cnd->rtnd", np.asarray(o_lat), w_v)
        idx = np.asarray(idx).reshape(rows, t, k)
        valid = np.asarray(valid).reshape(rows, t, k)
        for a in range(rows):
            for i in range(t):
                keep = idx[a, i][valid[a, i]]
                assert len(keep) == min(k, qpos[a, i] + 1)
                kk = np.concatenate([
                    np.einsum("sc,cnd->snd", lat[keep, :c], w_k),
                    np.broadcast_to(np.asarray(lat)[keep, None, c:],
                                    (len(keep), nh, r))], -1)
                val = np.einsum("sc,cnd->snd", lat[keep, :c], w_v)
                sc = np.einsum("nd,snd->ns", np.concatenate(
                    [q_nope[a, i], q_rope[a, i]], -1), kk) * 0.4
                pr = np.exp(sc - sc.max(-1, keepdims=True))
                pr /= pr.sum(-1, keepdims=True)
                np.testing.assert_allclose(
                    got[a, i], np.einsum("ns,snd->nd", pr, val),
                    rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("k", [1, 3, 7, 16, 40])
def test_the_thresholds_selection_is_top_ks(k):
    rng = np.random.default_rng(k)
    score = rng.normal(size=(9, 33)).astype(np.float32) * 10.0 ** \
        rng.integers(-3, 4, (9, 1))
    score[:, 20:] = np.where(rng.random((9, 13)) < 0.5, -np.inf,
                             score[:, 20:])
    score[3] = -np.inf
    score[4, 5:] = -np.inf
    score[5, ::2] = 0.0             # ties: the lower position wins
    score[6] = np.where(np.isfinite(score[6]), 1.5, -np.inf)
    keys, thr, ties = pa.select_threshold(jnp.asarray(score), k)
    mine = np.asarray(pa.selection_mask(keys, thr, ties)) \
        & np.isfinite(score)
    idx, valid = pa.select_topk(jnp.asarray(score), k)
    for row in range(9):
        want = set(np.asarray(idx)[row][np.asarray(valid)[row]].tolist())
        assert set(np.flatnonzero(mine[row]).tolist()) == want, row


@pytest.mark.parametrize("pos0,t", [(0, 1), (3, 1), (11, 1), (4, 6),
                                    (8, 6)])
def test_windowed_attention_walks_the_windows_pages_alone(pos0, t):
    rng = np.random.default_rng(3)
    nh, c, r = 2, 6, 2
    n = pos0 + t
    lat = jnp.asarray(rng.normal(size=(n, c + r)), jnp.float32)
    pages = [4, 9, 2, 7, 10]
    pool = _pool_with(lat, pages, c + r, rng)
    table = np.zeros((1, 6), np.int32)
    table[0, :5] = pages
    # the pages wholly behind the first query's window were given back
    gone = max(pos0 - WINDOW + 1, 0) // PAGE
    table[0, :gone] = 0
    pool = pool.at[0, pages[:gone]].set(jnp.nan)
    q = jnp.asarray(rng.normal(size=(1, t, nh, c + r)), jnp.float32)
    got, lse = pa.window_latent_attention(
        q, pool, 0, table, np.asarray([pos0]), np.asarray([t]), WINDOW, c,
        0.3)
    got, lse = np.asarray(got)[0], np.asarray(lse)[0]
    for i in range(t):
        lo, hi = max(pos0 + i - WINDOW + 1, 0), pos0 + i + 1
        s = np.einsum("nc,sc->ns", q[0, i], lat[lo:hi]) * 0.3
        p = np.exp(s - s.max(-1, keepdims=True))
        np.testing.assert_allclose(
            lse[i], np.mean(s.max(-1) + np.log(p.sum(-1))), rtol=2e-5)
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(got[i], p @ np.asarray(lat)[lo:hi, :c],
                                   rtol=2e-5, atol=2e-5)


# --- the experts -----------------------------------------------------------
def test_the_shares_with_the_shared_expert_once_are_the_uncut_layer():
    rng = np.random.default_rng(4)
    t, h, f, e, k, shares = 24, 16, 8, 16, 4, 4
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(h, e)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(e,)) * 0.3, jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(e, h, f)) * .3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e, f, h)) * .3, jnp.float32)
    shared = tuple(jnp.asarray(rng.normal(size=s) * .3, jnp.float32)
                   for s in ((h, f), (h, f), (f, h)))

    def layer(first, count, with_shared):
        return held_moe(x, router, wg[first:first + count],
                        wu[first:first + count], wd[first:first + count], k,
                        (first, count), scoring="sigmoid", select_bias=bias,
                        shared=shared if with_shared else None)

    whole, rows = layer(0, e, True)
    assert int(rows.sum()) == t * k
    per = e // shares
    parts = [layer(i * per, per, i == 0) for i in range(shares)]
    assert sum(int(r.sum()) for _, r in parts) == t * k
    np.testing.assert_allclose(sum(np.asarray(y) for y, _ in parts),
                               np.asarray(whole), rtol=2e-5, atol=2e-5)


def test_the_other_share_is_another_model(net, tokens):
    other = build(seed=3)
    other.config.experts_held = (4, 4)
    assert np.abs(np.asarray(other(tokens))
                  - np.asarray(net(tokens))).max() > 1e-3


@pytest.mark.parametrize("shape,width,tile", [
    # train-solar-open2-1chip: 8,192 tokens, 8 of 320 held, products of
    # [H 4096, F 1280]; train-olmoe-1chip-4k: 4,096 x 8 rows, 64 experts of
    # [2048, 1024]; the serving tick of dots3-note-prev: 268 tokens; the
    # one of Ling-3.0-flash: 64 decode rows beside a chunk row of 256
    (("solar", 8192, 8, 8, 320, 4096, 1280), 2560, (128, 4096, 256)),
    (("olmoe", 4096, 8, 64, 64, 2048, 1024), 32768, (128, 2048, 1024)),
    (("dots3-tick", 268, 8, 32, 256, 5120, 1536), 512, (128, 5120, 384)),
    (("ling3-tick", 320, 8, 128, 512, 2560, 768), 1024, (128, 2560, 768)),
])
def test_the_grouped_products_pick_the_tiles_they_picked(shape, width, tile):
    """A held expert of a serving tick gets ~8 rows (Ling's one or two):
    its window is the rows there are, rounded up to the kernels' 128-row
    tiles once (not a tile a group), and the training shapes keep the
    windows they had. The tiles are the whole contraction a step since
    PR 50 (OLMoE's were: the whole matrix, as before); Ling's expert is
    one step a visit."""
    _, t, top_k, held, e, h, f = shape
    assert held_window_rows(t, top_k, held, e) == width
    assert gmm.tile_for(width, h, f) == tile
    assert gmm.tile_for(width, f, h)[:2] == (128, f)


# --- the vocabulary in eighths ---------------------------------------------
def test_the_sliced_vocabulary_is_the_whole_ones_slice(tokens):
    whole = build()
    part = build(vocab_size=24)
    ws, wo = whole._decode_state()
    ps_, po = part._decode_state()
    # the slice's rows of the embedding and columns of the head
    po["embeddings.wte.weight"] = wo["embeddings.wte.weight"][24:48]
    po["lm_head.weight"] = wo["lm_head.weight"][:, 24:48]
    po["ln_f.weight"] = wo["ln_f.weight"]
    part.__dict__["_gen_state"] = (
        id(part.embeddings.wte.weight._value), ws, po)
    ids = tokens[:20] % 24
    np.testing.assert_allclose(np.asarray(part(ids)),
                               np.asarray(whole(ids + 24))[:, 24:48],
                               rtol=1e-5, atol=1e-5)
    eng = engine(part)
    rid = eng.submit(ids[:11], 5)
    out = eng.run()[rid]
    assert out.max() < 24


# --- preemption ------------------------------------------------------------
def test_a_preempted_slot_resumes_with_its_pools(net, tokens):
    alone = {}
    for a, n in ((0, 14), (7, 19)):
        eng = engine(net)
        rid = eng.submit(tokens[a:a + n], 16)
        alone[(a, n)] = eng.run()[rid]
    from paddle_tpu.profiler import registry

    before = registry().counter("serving/preemptions").value
    # 12 pages for two requests that need 8 and 9: one is preempted
    eng = engine(net, num_slots=2, pages_per_slot=10, num_pages=13)
    rids = {eng.submit(tokens[a:a + n], 16): (a, n) for a, n in alone}
    outs = eng.run()
    assert registry().counter("serving/preemptions").value > before
    for rid, key in rids.items():
        assert outs[rid].tolist() == alone[key].tolist()
    assert eng.pool.check_consistency() == []
    assert eng.pool.allocator.num_allocated == 0
    assert eng.pool.window_allocator.num_allocated == 0


# --- what is refused, by what it lacks --------------------------------------
def test_prefix_cache_spec_and_handoffs_are_refused_by_name(net, tokens):
    from paddle_tpu.serving.spec import SpecConfig

    with pytest.raises(NotImplementedError, match="windowed layer"):
        engine(net, prefix_cache=True)
    with pytest.raises(NotImplementedError, match="verify tick"):
        engine(net, spec=SpecConfig(draft_model=net, k=2))
    with pytest.raises(NotImplementedError, match="no head axis"):
        engine(net, kv_dtype="int8")
    eng = engine(net)
    with pytest.raises(NotImplementedError, match="hold_after_prefill"):
        eng.submit(tokens[:5], 2, hold_after_prefill=True)
    for call in (lambda: eng.export_held(0), lambda: eng.admit_prefilled({}),
                 lambda: eng.export_prefix_chain(tokens[:8]),
                 lambda: eng.import_prefix_chain({})):
        with pytest.raises(NotImplementedError, match="Pools of K and V"):
            call()
    with pytest.raises(NotImplementedError):
        eng.pool.shrink_slot(0, 0)


def test_gpt_still_says_what_its_forwards_lack():
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.models.gpt import _require_served_block

    with pytest.raises(NotImplementedError) as e:
        _require_served_block(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
            max_seq_len=16, moe_num_experts=4, moe_dropless=True,
            ffn="swiglu", bias=False))
    assert "models/dots3.py" in str(e.value)
    assert "training only" not in str(e.value)


def test_the_engine_reads_the_caches_from_the_model(net):
    from paddle_tpu.models import GPT, GPTConfig

    spec = net.cache_spec()
    record = spec.pop("tick_record")
    assert spec == {"kind": "latent", "full_layers": 2, "latent_width": 12,
                    "index_width": 8, "window_layers": 3,
                    "window_width": 16, "window": WINDOW}
    eng = engine(net)
    assert isinstance(eng.tick_record, record)
    assert isinstance(eng.pool.pools, LatentPools)
    assert eng.pool.pools.latent.shape == (2, 49, 12, PAGE)
    assert eng.pool.pools.index_k.shape == (2, 49, 8, PAGE)
    gpt = GPT(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=32, loop_steps=2,
                        norm="rmsnorm", position="rope", bias=False,
                        ffn="swiglu", tie_word_embeddings=False))
    from paddle_tpu.models.tick import LoopRecord

    assert gpt.cache_spec() == {"kind": "kv", "layers": 4, "heads": 2,
                                "head_dim": 16, "loop_steps": 2,
                                "tick_record": LoopRecord}


def test_a_lazy_model_draws_every_layers_own_weights():
    paddle.seed(11)
    with paddle.LazyGuard():
        lazy = Dots3(Dots3Config.tiny(experts_held=(4, 4)))
    lazy.eval()
    lazy.bfloat16()
    stacked, other = lazy._decode_state()
    assert sorted(stacked) == [f"layer{i}" for i in range(5)]
    assert stacked["layer2"]["ffn.w_gate"].shape == (4, 32, 16)
    assert stacked["layer0"]["attn.idx_q.weight"].dtype == jnp.bfloat16
    assert "attn.idx_q.weight" not in stacked["layer2"]
    assert "ffn.w_gate" not in stacked["layer0"]
    assert float(jnp.std(stacked["layer1"]["ffn.select_bias"]
                         .astype(jnp.float32))) > 0.01
    assert other["lm_head.weight"].shape == (32, 96)
    eng = engine(lazy)
    rid = eng.submit(np.arange(9, dtype=np.int32), 3)
    assert len(eng.run()[rid]) == 3
