"""int8 KV-page tests (ISSUE 12): quantize/dequant round-trip units
(amax edge cases), scale lifecycle across COW/share/preempt/reset
edges, engine parity-on-tolerance vs the f32 engine across the PR-5/6
matrix, and the config-validation surface.

Regime note (measured, see BENCH_SERVE_r12.json): the parity-on-
tolerance assertions run on STANDARD-init (0.02) untrained models.
With the serving benches' usual 0.2-scale init, untrained attention
logits saturate and the greedy argmax sits on knife-edge ties — a
sub-1% cache perturbation flips tokens at ~10%/step there, which
measures the regime's chaos, not the quantizer (the same reasoning as
serve_bench's spec-decode draft-friendly-regime note). At 0.02 init
the per-step argmax margin is real and the measured match rate is 1.0
over hundreds of tokens.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.serving import ServingConfig, ServingEngine

pytestmark = pytest.mark.serving

import jax

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops.paged_attention import (  # noqa: E402
    paged_kv_scatter, ragged_paged_attention)


def _model(vocab=128, hidden=64, layers=4, heads=4, msl=256):
    paddle.seed(0)
    net = GPT(GPTConfig(vocab_size=vocab, hidden_size=hidden,
                        num_layers=layers, num_heads=heads,
                        max_seq_len=msl))
    net.eval()
    return net


def _prompts(net, n, lens, seed=7):
    rng = np.random.RandomState(seed)
    v = net.config.vocab_size
    return [rng.randint(0, v, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(n)]


def _run(net, prompts, max_new, kv_dtype, *, slots=4, page_size=8,
         pages_per_slot=None, prefix_cache=True, num_pages=0):
    pps = pages_per_slot or -(-(max(len(p) for p in prompts) + max_new)
                              // page_size)
    eng = ServingEngine(net, ServingConfig(
        num_slots=slots, page_size=page_size, pages_per_slot=pps,
        num_pages=num_pages, prefix_cache=prefix_cache,
        kv_dtype=kv_dtype))
    rids = [eng.submit(p, max_new) for p in prompts]
    res = eng.run()
    return [res[r] for r in rids], eng


def _match_rate(a_list, b_list):
    tot = mat = 0
    for a, b in zip(a_list, b_list):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            tot += 1
            mat += int(x == y)
    return mat / max(tot, 1), tot


# ---------------------------------------------------------------------------
# quantize/dequant round-trip units (paged_kv_scatter)
# ---------------------------------------------------------------------------
class TestScatterUnits:
    def _pools(self, P=4, ps=4, NH=2, D=8):
        return (jnp.zeros((P, ps, NH, D), jnp.int8),
                jnp.zeros((P, NH), jnp.float32))

    def test_all_zero_page_keeps_scale_zero(self):
        pool, scale = self._pools()
        pool, scale = paged_kv_scatter(
            pool, scale, np.array([1], np.int32), np.array([0], np.int32),
            jnp.zeros((1, 2, 8), jnp.float32))
        assert float(jnp.abs(scale).max()) == 0.0
        assert int(jnp.abs(pool).max()) == 0

    def test_single_outlier_head_isolated(self):
        # head 0 carries a 100x outlier; head 1 stays small. Per-head
        # scales mean head 1's precision is set by ITS amax, not the
        # outlier's.
        pool, scale = self._pools()
        vals = np.full((1, 2, 8), 0.01, np.float32)
        vals[0, 0, 3] = 100.0
        pg = np.array([2], np.int32)
        off = np.array([1], np.int32)
        pool, scale = paged_kv_scatter(pool, scale, pg, off,
                                       jnp.asarray(vals))
        deq = np.asarray(pool, np.float32)[2, 1] * \
            np.asarray(scale)[2][:, None]
        assert abs(deq[0, 3] - 100.0) <= 100.0 / 254 + 1e-6
        # head 1 error bounded by its own (tiny) scale, not the outlier
        assert np.abs(deq[1] - 0.01).max() <= 0.01 / 254 + 1e-6

    def test_rescale_on_growth_keeps_old_tokens(self):
        # write a small token, then a 10x-larger one into the SAME
        # page: the growth re-quantizes the resident content, whose
        # dequant must stay within ~1.5 quantization steps of the
        # original (0.5 from the first write + 0.5-1 from one rescale)
        pool, scale = self._pools()
        rng = np.random.RandomState(0)
        small = rng.randn(1, 2, 8).astype(np.float32) * 0.1
        big = rng.randn(1, 2, 8).astype(np.float32) * 1.0
        pg = np.array([1], np.int32)
        pool, scale = paged_kv_scatter(pool, scale, pg,
                                       np.array([0], np.int32),
                                       jnp.asarray(small))
        pool, scale = paged_kv_scatter(pool, scale, pg,
                                       np.array([1], np.int32),
                                       jnp.asarray(big))
        s = np.asarray(scale)[1]                      # [NH] final scales
        deq0 = np.asarray(pool, np.float32)[1, 0] * s[:, None]
        assert np.abs(deq0 - small[0]).max() <= 1.5 * s.max() + 1e-7
        # steady state: same-scale rewrite is an exact no-op
        pool2, scale2 = paged_kv_scatter(pool, scale, pg,
                                         np.array([2], np.int32),
                                         jnp.asarray(small))
        assert np.array_equal(np.asarray(pool2)[1, :2],
                              np.asarray(pool)[1, :2])
        assert np.array_equal(np.asarray(scale2)[1], s)

    def test_null_page_scale_stays_zero(self):
        pool, scale = self._pools()
        pool, scale = paged_kv_scatter(
            pool, scale, np.array([0], np.int32),
            np.array([2], np.int32),
            jnp.full((1, 2, 8), 5.0, jnp.float32))
        assert float(jnp.abs(scale[0]).max()) == 0.0

    def test_f32_path_is_plain_scatter(self):
        pool = jnp.zeros((4, 4, 2, 8), jnp.float32)
        vals = jnp.full((1, 2, 8), 3.25, jnp.float32)
        out, sc = paged_kv_scatter(pool, None, np.array([1], np.int32),
                                   np.array([0], np.int32), vals)
        assert sc is None
        assert np.array_equal(np.asarray(out)[1, 0], np.asarray(vals)[0])


# ---------------------------------------------------------------------------
# dequant inside the shared gather (both impls)
# ---------------------------------------------------------------------------
class TestQuantizedAttention:
    def _quantized_pools(self, seed=0, P=6, ps=8, NH=4, D=16, toks=20):
        rng = np.random.RandomState(seed)
        kf = jnp.zeros((P, ps, NH, D), jnp.float32)
        vf = jnp.zeros((P, ps, NH, D), jnp.float32)
        kq = jnp.zeros((P, ps, NH, D), jnp.int8)
        vq = jnp.zeros((P, ps, NH, D), jnp.int8)
        ks = jnp.zeros((P, NH), jnp.float32)
        vs = jnp.zeros((P, NH), jnp.float32)
        table = np.array([[1, 2, 3]], np.int32)
        for t in range(toks):
            pg = np.array([table[0, t // ps]], np.int32)
            off = np.array([t % ps], np.int32)
            kk = jnp.asarray(rng.randn(1, NH, D).astype(np.float32))
            vv = jnp.asarray(rng.randn(1, NH, D).astype(np.float32))
            kf, _ = paged_kv_scatter(kf, None, pg, off, kk)
            vf, _ = paged_kv_scatter(vf, None, pg, off, vv)
            kq, ks = paged_kv_scatter(kq, ks, pg, off, kk)
            vq, vs = paged_kv_scatter(vq, vs, pg, off, vv)
        return (kf, vf), (kq, vq, ks, vs), jnp.asarray(table), rng

    def test_int8_gather_close_to_f32(self):
        (kf, vf), (kq, vq, ks, vs), table, rng = self._quantized_pools()
        q = jnp.asarray(rng.randn(1, 1, 4, 16).astype(np.float32))
        pos0 = np.array([19], np.int32)
        tl = np.array([1], np.int32)
        of = ragged_paged_attention(q, kf, vf, table, pos0, tl)
        oq = ragged_paged_attention(q, kq, vq, table, pos0, tl,
                                    k_scale=ks, v_scale=vs)
        assert np.abs(np.asarray(of) - np.asarray(oq)).max() < 0.05

    def test_pallas_int8_matches_xla_int8(self):
        _, (kq, vq, ks, vs), table, rng = self._quantized_pools()
        q = jnp.asarray(rng.randn(1, 1, 4, 16).astype(np.float32))
        pos0 = np.array([19], np.int32)
        tl = np.array([1], np.int32)
        ox = ragged_paged_attention(q, kq, vq, table, pos0, tl,
                                    k_scale=ks, v_scale=vs, impl="xla")
        op = ragged_paged_attention(q, kq, vq, table, pos0, tl,
                                    k_scale=ks, v_scale=vs,
                                    impl="pallas")
        np.testing.assert_allclose(np.asarray(ox), np.asarray(op),
                                   rtol=2e-5, atol=2e-5)

    def test_f32_pool_keeps_precision_under_bf16_query(self):
        # regression (review): kv_dtype='f32' under a bf16 model must
        # contract at f32 — downcasting the gathered pool to the query
        # dtype would throw away the precision the 2x HBM paid for.
        # The f32-pool/bf16-query result must match the all-f32
        # reference strictly better than the bf16-pool one does.
        (kf, vf), _, table, rng = self._quantized_pools()
        q32 = jnp.asarray(rng.randn(1, 1, 4, 16).astype(np.float32))
        q16 = q32.astype(jnp.bfloat16)
        pos0 = np.array([19], np.int32)
        tl = np.array([1], np.int32)
        ref = np.asarray(ragged_paged_attention(q32, kf, vf, table,
                                                pos0, tl), np.float32)
        hi = ragged_paged_attention(q16, kf, vf, table, pos0, tl)
        lo = ragged_paged_attention(q16, kf.astype(jnp.bfloat16),
                                    vf.astype(jnp.bfloat16), table,
                                    pos0, tl)
        assert hi.dtype == jnp.bfloat16 and lo.dtype == jnp.bfloat16
        err_hi = np.abs(np.asarray(hi, np.float32) - ref).max()
        err_lo = np.abs(np.asarray(lo, np.float32) - ref).max()
        assert err_hi <= err_lo, (err_hi, err_lo)

    def test_null_pages_read_as_zero(self):
        # a row whose table is all-null must attend only masked keys —
        # with scale 0 the int8 garbage dequantizes to exact zeros
        _, (kq, vq, ks, vs), _, rng = self._quantized_pools()
        q = jnp.asarray(rng.randn(1, 1, 4, 16).astype(np.float32))
        table0 = jnp.zeros((1, 3), jnp.int32)
        out = ragged_paged_attention(q, kq, vq, table0,
                                     np.array([0], np.int32),
                                     np.array([1], np.int32),
                                     k_scale=ks, v_scale=vs)
        assert np.all(np.isfinite(np.asarray(out)))


# ---------------------------------------------------------------------------
# engine parity-on-tolerance + scale lifecycle
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def small_net():
    return _model()


class TestEngineInt8:
    def test_token_match_vs_f32(self, small_net):
        # mixed lengths incl. an exact-capacity rider (16 + 16 == the
        # 32-token slot capacity at ps=8, pps=4)
        prompts = _prompts(small_net, 6, (5, 9, 16, 8))
        f32, _ = _run(small_net, prompts, 16, None, pages_per_slot=4)
        q, eng = _run(small_net, prompts, 16, "int8", pages_per_slot=4)
        rate, tot = _match_rate(f32, q)
        assert tot >= 90
        assert rate >= 0.99, f"match rate {rate} over {tot} tokens"
        assert eng.pool.quantized and eng.pool.k.dtype == jnp.int8

    def test_single_trace_and_one_site(self, small_net):
        from paddle_tpu.profiler import recompile
        prompts = _prompts(small_net, 3, (6, 11))
        _, eng = _run(small_net, prompts, 8, "int8")
        assert len(eng.compiled_sites) == 1
        counts = recompile.trace_counts()
        assert counts.get(eng._tick_site, 0) == 1, counts

    def test_cached_vs_uncached_bitwise_int8(self, small_net):
        # page-aligned shared prefix (32 tokens == 4 pages at ps=8):
        # aliased pages hold the SAME int8 content and scales the first
        # tenant wrote, so int8 cached == int8 uncached byte-for-byte
        rng = np.random.RandomState(3)
        v = small_net.config.vocab_size
        system = rng.randint(0, v, (32,)).astype(np.int32)
        prompts = [np.concatenate([system,
                                   rng.randint(0, v, (4,))
                                   .astype(np.int32)])
                   for _ in range(4)]
        from paddle_tpu.profiler import registry
        h0 = registry().counter("serving/prefix_hit_tokens").value
        on, _ = _run(small_net, prompts, 8, "int8", prefix_cache=True)
        hits = registry().counter(
            "serving/prefix_hit_tokens").value - h0
        off, _ = _run(small_net, prompts, 8, "int8", prefix_cache=False)
        assert hits > 0
        for a, b in zip(on, off):
            assert np.array_equal(a, b)

    def test_cow_and_preempt_match(self, small_net):
        # COW divergence (shared prefix diverging mid-page) + pool
        # pressure forcing preemption, vs the f32 engine on the same
        # workload — scales must travel with pages through both edges
        rng = np.random.RandomState(5)
        v = small_net.config.vocab_size
        base = rng.randint(0, v, (12,)).astype(np.int32)
        prompts = []
        for i in range(5):
            p = base.copy()
            if i:
                p[10:] = rng.randint(0, v, (2,))  # diverge mid-page 2
            prompts.append(np.concatenate(
                [p, rng.randint(0, v, (4,)).astype(np.int32)]))
        from paddle_tpu.profiler import registry
        c0 = registry().counter("cache_share/cow_copies").value
        p0 = registry().counter("serving/preemptions").value
        kw = dict(slots=3, page_size=8, pages_per_slot=4, num_pages=8)
        f32, _ = _run(small_net, prompts, 10, None, **kw)
        q, _ = _run(small_net, prompts, 10, "int8", **kw)
        assert registry().counter("cache_share/cow_copies").value > c0
        assert registry().counter("serving/preemptions").value > p0
        rate, tot = _match_rate(f32, q)
        assert rate >= 0.99, f"match rate {rate} over {tot} tokens"

    def test_stale_scale_reset_on_reuse(self, small_net):
        # poison the scales of every FREE page with a huge value, run a
        # workload that recycles pages — outputs must equal the
        # unpoisoned run bitwise, proving recycled pages' scales are
        # reset before their first write (a stale running-max would
        # quantize every new tenant's KV at the poisoned scale)
        prompts = _prompts(small_net, 6, (7, 13), seed=11)
        clean, _ = _run(small_net, prompts, 12, "int8", slots=2)
        pps = -(-25 // 8)
        eng = ServingEngine(small_net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=pps,
            kv_dtype="int8"))
        free = np.asarray(sorted(eng.pool.allocator._free), np.int32)
        eng.pool.pools = eng.pool.pools._replace(
            k_scale=eng.pool.k_scale.at[:, free].set(1e6),
            v_scale=eng.pool.v_scale.at[:, free].set(1e6))
        rids = [eng.submit(p, 12) for p in prompts]
        res = eng.run()
        poisoned = [res[r] for r in rids]
        for a, b in zip(clean, poisoned):
            assert np.array_equal(a, b)

    def test_pool_args_sees_overflow_reset(self, small_net):
        # regression (review): the tick args must capture the scale
        # arrays AFTER take_fresh ran — its overflow path eagerly
        # rewrites the pools' scales, and capturing first would
        # dispatch the stale (un-reset) arrays and then clobber the
        # reset with the tick's output
        eng = ServingEngine(small_net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=2,
            kv_dtype="int8"))
        eng._fresh_cap = 1
        eng.pool._fresh = [1, 2, 3]
        poison = np.array([1, 2, 3], np.int32)
        eng.pool.pools = eng.pool.pools._replace(
            k_scale=eng.pool.k_scale.at[:, poison].set(7.0),
            v_scale=eng.pool.v_scale.at[:, poison].set(7.0))
        pools, fresh = eng._pool_args()
        assert np.asarray(fresh).tolist() == [1]
        # the overflow pages (2, 3) were reset eagerly, and the
        # CAPTURED arrays already reflect it
        assert np.all(np.asarray(pools.k_scale)[:, 2:4] == 0.0)
        assert np.all(np.asarray(pools.v_scale)[:, 2:4] == 0.0)
        # in-tick reset's job
        assert np.all(np.asarray(pools.k_scale)[:, 1] == 7.0)

    def test_claim_fresh_drops_duplicates(self):
        # regression (review): an alloc → preempt-release → realloc
        # cycle within one scheduler step lists the same page id twice
        # in the pending-reset list; a COW claim must drop EVERY
        # occurrence or the next tick still zeroes the copied scales
        from paddle_tpu.serving.paged_cache import PagePool
        import jax.numpy as jnp
        pool = PagePool(1, 6, 4, 2, 4, 2, 2, dtype=jnp.int8)
        a = pool._alloc(2)              # e.g. [5, 4]
        pool.allocator.free(a)
        b = pool._alloc(1)              # re-allocates one of them
        # listed at alloc, at zero-free (ISSUE 18 on_zero hook), and
        # at realloc — claim must drop every occurrence
        assert pool._fresh.count(b[0]) >= 2
        pool.claim_fresh(b[0])
        assert b[0] not in pool._fresh
        # the other freshly-listed page is untouched
        assert any(p != b[0] for p in pool._fresh)

    def test_int8_schedule_independent_across_admission_orders(
            self, small_net):
        # ISSUE 18 satellite: a page's scales die with its last
        # reference (PageAllocator.on_zero), so WHICH recycled page a
        # request lands on — a pure scheduling artifact of admission
        # order — can never tint its quantized output. Two admission
        # orders of the same page-recycling workload must produce
        # bitwise-identical per-request outputs.
        prompts = _prompts(small_net, 4, (9, 17, 7, 13), seed=13)
        fwd, _ = _run(small_net, prompts, 10, "int8", slots=2)
        rev, _ = _run(small_net, list(reversed(prompts)), 10, "int8",
                      slots=2)
        for a, b in zip(fwd, reversed(rev)):
            assert np.array_equal(a, b)

    def test_cow_copy_carries_scales(self):
        from paddle_tpu.serving.paged_cache import Pools
        k = jnp.arange(2 * 4 * 2 * 2 * 2, dtype=jnp.int8).reshape(
            2, 4, 2, 2, 2)
        s = jnp.arange(2 * 4 * 2, dtype=jnp.float32).reshape(2, 4, 2)
        out = Pools(k, k, s, s * 2).copy_page(jnp.int32(1), jnp.int32(3))
        assert np.array_equal(np.asarray(out.k)[:, 3], np.asarray(k)[:, 1])
        assert np.array_equal(np.asarray(out.k_scale)[:, 3],
                              np.asarray(s)[:, 1])
        assert np.array_equal(np.asarray(out.v_scale)[:, 3],
                              np.asarray(s * 2)[:, 1])

    def test_bf16_pool(self, small_net):
        prompts = _prompts(small_net, 3, (6, 10), seed=2)
        b16, eng = _run(small_net, prompts, 8, "bf16")
        assert eng.pool.k.dtype == jnp.bfloat16
        f32, _ = _run(small_net, prompts, 8, None)
        rate, _ = _match_rate(f32, b16)
        assert rate >= 0.99

    def test_generate_paged_kv_dtype(self, small_net):
        ids = _prompts(small_net, 2, (8,), seed=9)
        batch = np.stack(ids)
        out_f, _ = small_net.generate(paddle.to_tensor(batch),
                                      max_new_tokens=8, paged=True)
        out_q, _ = small_net.generate(paddle.to_tensor(batch),
                                      max_new_tokens=8, paged=True,
                                      kv_dtype="int8")
        rate, _ = _match_rate(np.asarray(out_f.numpy()),
                              np.asarray(out_q.numpy()))
        assert rate >= 0.99

    def test_pool_bytes_quartered(self, small_net):
        _, eng_f = _run(small_net, _prompts(small_net, 1, (6,)), 4, None)
        _, eng_q = _run(small_net, _prompts(small_net, 1, (6,)), 4,
                        "int8")
        f_bytes = eng_f.pool.k.nbytes + eng_f.pool.v.nbytes
        q_bytes = (eng_q.pool.k.nbytes + eng_q.pool.v.nbytes
                   + eng_q.pool.k_scale.nbytes
                   + eng_q.pool.v_scale.nbytes)
        assert q_bytes < 0.3 * f_bytes, (q_bytes, f_bytes)


class TestValidation:
    def test_unknown_kv_dtype(self, small_net):
        with pytest.raises(ValueError, match="kv_dtype"):
            ServingEngine(small_net, ServingConfig(kv_dtype="fp4"))

    def test_dense_generate_rejects_kv_dtype(self, small_net):
        with pytest.raises(ValueError, match="paged"):
            small_net.generate(paddle.to_tensor(
                np.zeros((1, 4), np.int32)), max_new_tokens=4,
                kv_dtype="int8")


@pytest.mark.slow
class TestSpecInt8:
    def test_spec_int8_matches_plain_int8(self, small_net):
        # under int8 KV the spec engine still emits the target's argmax
        # stream as computed on the quantized cache, but rejected-draft
        # writes can raise page scales the plain engine never sees —
        # parity is tolerance, not bitwise (stated in serving/spec.py)
        from paddle_tpu.serving import SpecConfig
        import benchmarks.serve_bench as sb

        draft = sb.build_early_exit_draft(small_net, 1)
        prompts = _prompts(small_net, 4, (6, 10), seed=13)
        pps = -(-26 // 8)
        plain, _ = _run(small_net, prompts, 16, "int8",
                        pages_per_slot=pps)
        eng = ServingEngine(small_net, ServingConfig(
            num_slots=4, page_size=8, pages_per_slot=pps,
            kv_dtype="int8", spec=SpecConfig(draft_model=draft, k=3)))
        rids = [eng.submit(p, 16) for p in prompts]
        res = eng.run()
        spec = [res[r] for r in rids]
        rate, tot = _match_rate(plain, spec)
        assert len(eng.compiled_sites) == 2
        assert rate >= 0.99, f"spec-int8 match {rate} over {tot}"
