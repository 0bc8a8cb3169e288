"""Speculative decoding on the paged serving engine (serving/spec.py).

THE load-bearing contract is the classic greedy-acceptance invariant:
speculative greedy output is BITWISE identical to non-speculative
greedy paged decode (itself bitwise vs dense ``generate()``), for ANY
draft model — the emitted stream is always the target's own argmax
(accepted drafts equal it by definition, the correction token is it) —
so the invariant is pinned at BOTH ends of the accept-rate spectrum: a
twin draft (identical weights, ~100% acceptance, exercising multi-
token emission + rewind) and an independent tiny draft (~0% acceptance,
exercising the all-rejected path). Compile-heavy cases (engines are
expensive to trace; the tier-1 cap is saturated) stay lean or
slow-marked — the Poisson workload runs in the CI serve-smoke leg.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPT, GPTConfig, gpt_tiny
from paddle_tpu.ops import decoding as D
from paddle_tpu.serving import (PagePool, ServingConfig, ServingEngine,
                                SpecConfig)

pytestmark = pytest.mark.serving


def _net(seed=0):
    """initializer_range=0.2: varied greedy output (test_serving rule —
    a collapsed argmax sequence would hide KV-placement bugs)."""
    paddle.seed(seed)
    net = gpt_tiny(initializer_range=0.2)
    net.eval()
    return net


def _small_draft(seed=7):
    """Independent 2-layer draft: random weights, so its argmax almost
    never matches the target's — the all-rejected regime."""
    paddle.seed(seed)
    net = GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=64,
                        initializer_range=0.2))
    net.eval()
    return net


def _dense(net, prompt, max_new, **kw):
    ids, _ = net.generate(paddle.to_tensor(prompt[None]),
                          max_new_tokens=max_new, **kw)
    return ids.numpy()[0]


def test_spec_accept_length_unit():
    d = jnp.asarray(np.array([[5, 6, 7],     # all match
                              [5, 9, 7],     # mismatch at 1
                              [9, 6, 7],     # mismatch at 0
                              [5, 6, 7]], np.int32))
    t = jnp.asarray(np.array([[5, 6, 7],
                              [5, 6, 7],
                              [5, 6, 7],
                              [5, 6, 9]], np.int32))
    n = jnp.asarray(np.array([3, 3, 3, 1], np.int32))
    acc = np.asarray(D.spec_accept_length(d, t, n))
    # row 3: only 1 draft offered, and it matches -> 1 (the k=3-wide
    # row never counts unoffered positions)
    np.testing.assert_array_equal(acc, [3, 1, 0, 1])
    # n_draft == 0: a plain decode row riding a spec tick accepts 0
    acc0 = np.asarray(D.spec_accept_length(
        d, t, jnp.zeros((4,), jnp.int32)))
    np.testing.assert_array_equal(acc0, [0, 0, 0, 0])


def test_page_shrink_is_refcount_safe():
    """shrink_slot (the speculative-rewind path) drops only the slot's
    own reference on tail pages: a page the prefix index still holds
    survives; a solely-held page returns to the free list; the zeroed
    table tail can never be gathered."""
    pool = PagePool(num_layers=1, num_pages=8, page_size=4, num_heads=1,
                    head_dim=2, num_slots=1, pages_per_slot=4,
                    prefix_cache=True)
    assert pool.grow_slot(0, 4)
    pages = [int(p) for p in pool.tables[0]]
    # index the first three pages' chunk chain (one extra ref each)
    pool.prefix.insert(np.arange(12, dtype=np.int32), pages[:3])
    with pytest.raises(ValueError):
        pool.shrink_slot(0, -1)
    assert pool.shrink_slot(0, 4) == 0            # no-op
    assert pool.shrink_slot(0, 2) == 2            # drop pages[2:]
    assert pool.slot_pages(0) == 2
    assert (pool.tables[0, 2:] == 0).all()
    # pages[2] still indexed -> alive; pages[3] solely held -> freed
    assert pool.allocator.refcount(pages[2]) == 1
    assert pool.allocator.refcount(pages[3]) == 0
    # regrow hands back fresh pages without touching the survivor
    assert pool.grow_slot(0, 1)
    assert pool.allocator.refcount(pages[2]) == 1
    pool.release_slot(0)
    assert pool.prefix.evict_for(3) == 3          # index refs settle
    assert pool.allocator.num_allocated == 0


class TestSpecBitwiseInvariant:
    def test_twin_draft_parity_sites_and_amortization(self):
        """Twin draft (identical weights => near-total acceptance):
        mixed-length requests through two slots, slot reuse — every
        output bitwise equal to dense generate() AND to the plain
        (non-speculative) engine; the dispatch-site contract is
        exactly {draft tick, verify tick}, each traced ONCE; accepted
        tokens actually flowed (the multi-token emission + rewind
        paths ran, not just the k_s=0 fallback)."""
        from paddle_tpu.profiler import recompile, registry

        net = _net()
        twin = _net()                 # same seed -> identical weights
        cfgkw = dict(num_slots=2, page_size=8, pages_per_slot=3,
                     prefill_chunk=8)
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 128, (t,)).astype(np.int32)
                   for t in (8, 16, 8)]
        plain = ServingEngine(net, ServingConfig(**cfgkw))
        spec = ServingEngine(net, ServingConfig(
            spec=SpecConfig(draft_model=twin, k=3), **cfgkw))
        acc0 = registry().counter("serving/spec_accepted_tokens").value
        p_rids = [plain.submit(p, 24 - len(p)) for p in prompts]
        s_rids = [spec.submit(p, 24 - len(p)) for p in prompts]
        p_out, s_out = plain.run(), spec.run()
        for p, pr, sr in zip(prompts, p_rids, s_rids):
            want = _dense(net, p, 24 - len(p))
            assert len(set(want.tolist())) >= 4   # varied => real signal
            np.testing.assert_array_equal(p_out[pr], want)
            np.testing.assert_array_equal(s_out[sr], want)
        assert registry().counter(
            "serving/spec_accepted_tokens").value > acc0
        assert set(spec.compiled_sites) == \
            {spec._tick_site, spec._draft.site}
        counts = recompile.trace_counts()
        assert all(counts[site] == 1 for site in spec.compiled_sites)
        retraces = [r for r in recompile.retraces()
                    if r["site"].startswith("serving.")]
        assert not retraces

    def test_all_rejected_draft_still_bitwise(self):
        """An independent random draft accepts ~nothing — the engine
        must degrade to one correction token per verify tick with
        output still bitwise-dense (rejected tails rewind cleanly)."""
        net = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=3,
            prefill_chunk=8,
            spec=SpecConfig(draft_model=_small_draft(), k=4)))
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, 128, (t,)).astype(np.int32)
                   for t in (8, 16)]
        rids = [eng.submit(p, 24 - len(p)) for p in prompts]
        out = eng.run()
        for p, rid in zip(prompts, rids):
            np.testing.assert_array_equal(out[rid],
                                          _dense(net, p, 24 - len(p)))

    def test_preempt_mid_speculation_rewind(self):
        """Pool smaller than residency: preemption fires BETWEEN verify
        rounds with speculation live — the victim's accepted frontier
        requeues as prompt, its draft cache resets, the re-admission
        re-feeds, and every output stays bitwise-dense."""
        from paddle_tpu.profiler import registry

        net = _net()
        twin = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=3, num_pages=5,
            prefill_chunk=8, spec=SpecConfig(draft_model=twin, k=3)))
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 128, (8,)).astype(np.int32)
                   for _ in range(3)]
        pre0 = registry().counter("serving/preemptions").value
        rids = [eng.submit(p, 16) for p in prompts]
        out = eng.run()
        assert registry().counter("serving/preemptions").value > pre0
        for p, rid in zip(prompts, rids):
            np.testing.assert_array_equal(out[rid], _dense(net, p, 16))

    def test_prefix_cache_and_exact_capacity(self):
        """(a) Shared system prompt under spec + prefix cache: aliased
        pages and speculation compose bitwise, in BOTH admission
        orders (the reversed batch re-aliases the first batch's cached
        pages). (b) COW divergence: a prompt departing from a cached
        chunk MID-page copy-on-writes the tail page with speculation
        live. (c) A request finishing at EXACT slot capacity
        (9 + 24 - 1 == 32) with a co-resident — the capacity clamp
        keeps k_s in range and the finish publishes clean pages."""
        from paddle_tpu.profiler import registry

        net = _net()
        twin = _net()
        rng = np.random.RandomState(9)
        system = rng.randint(0, 128, (16,)).astype(np.int32)
        prompts = [np.concatenate(
            [system, rng.randint(0, 128, (8,)).astype(np.int32)])
            for _ in range(4)]
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=5,
            prefill_chunk=8, prefix_cache=True,
            spec=SpecConfig(draft_model=twin, k=3)))
        hit0 = registry().counter("serving/prefix_hit_tokens").value
        for order in (prompts, list(reversed(prompts))):
            rids = [eng.submit(p, 8) for p in order]
            out = eng.run()
            for p, rid in zip(order, rids):
                np.testing.assert_array_equal(out[rid],
                                              _dense(net, p, 8))
        assert registry().counter(
            "serving/prefix_hit_tokens").value > hit0
        # (b) mid-page divergence: COW fires while speculating
        cow0 = registry().counter("cache_share/cow_copies").value
        a = rng.randint(0, 128, (16,)).astype(np.int32)
        ra = eng.submit(a, 8)
        eng.run()
        b = np.concatenate([a[:12], (a[12:] + 1) % 128]).astype(np.int32)
        rb = eng.submit(b, 8)
        out_b = eng.run()[rb]
        assert registry().counter(
            "cache_share/cow_copies").value > cow0
        np.testing.assert_array_equal(out_b, _dense(net, b, 8))
        # (b) exact-capacity finish
        cap_eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=4,
            prefill_chunk=8, spec=SpecConfig(draft_model=twin, k=3)))
        a = rng.randint(0, 128, (9,)).astype(np.int32)
        b = rng.randint(0, 128, (8,)).astype(np.int32)
        ra = cap_eng.submit(a, 24)    # 9 + 24 - 1 == 32 == capacity
        cap_eng.submit(b, 25)
        np.testing.assert_array_equal(cap_eng.run()[ra],
                                      _dense(net, a, 24))

    def test_eos_mid_draft_stops_exactly(self):
        """EOS discovered inside an accepted draft run truncates the
        emission at the EOS token (spec mode syncs per tick, so there
        is no lag window) — the visible stream equals the dense path's
        up to its freeze point."""
        net = _net()
        twin = _net()
        toks = np.random.RandomState(5).randint(0, 128, (6,)) \
            .astype(np.int32)
        eos = int(_dense(net, toks, 4)[2])
        want = list(_dense(net, toks, 12, eos_token_id=eos))
        cut = want.index(eos) + 1 if eos in want else len(want)
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=3,
            prefill_chunk=8, eos_token_id=eos,
            spec=SpecConfig(draft_model=twin, k=3)))
        rid = eng.submit(toks, 12)
        assert list(eng.run()[rid]) == want[:cut]


class TestSpecObservability:
    def test_accept_metrics_events_and_breakdown(self):
        """Accept-rate accounting: counters/gauge/histogram move, the
        draft -> verify -> accept lifecycle events are present and
        ordered per request with accepted <= drafted, the latency
        breakdown stays complete with its buckets summing to total,
        and it folds the spec counts in."""
        from paddle_tpu.profiler import event_log, registry
        from paddle_tpu.profiler.events import breakdown_from_events

        net = _net()
        twin = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=3,
            prefill_chunk=8, spec=SpecConfig(draft_model=twin, k=3)))
        a0 = registry().counter("serving/spec_accepted_tokens").value
        d0 = registry().counter("serving/spec_drafted_tokens").value
        h0 = registry().histogram("serving/spec_accept_len").count
        rng = np.random.RandomState(3)
        rid = eng.submit(rng.randint(0, 128, (8,)).astype(np.int32), 16)
        eng.run()
        acc = registry().counter("serving/spec_accepted_tokens").value - a0
        drf = registry().counter("serving/spec_drafted_tokens").value - d0
        assert 0 < acc <= drf
        assert registry().histogram("serving/spec_accept_len").count > h0
        rate = registry().gauge("serving/spec_accept_rate").value
        assert rate is not None and 0.0 <= rate <= 1.0
        evs = [e for e in event_log().events(rid=rid)
               if e.attrs.get("eng") == eng._eng_id]
        kinds = [e.kind for e in evs]
        assert kinds.index("draft") < kinds.index("verify") \
            < kinds.index("accept")
        accepts = [e for e in evs if e.kind == "accept"]
        assert accepts
        for e in accepts:
            assert 0 <= e.attrs["accepted"] <= e.attrs["drafted"]
        b = breakdown_from_events(evs)    # this engine's events only
        assert b["complete"] and b["tokens"] == 16
        assert b["spec_drafted"] >= b["spec_accepted"] > 0
        buckets = b["queue_wait_ms"] + b["prefill_ms"] \
            + b["decode_ms"] + b["preempted_ms"]
        assert buckets == pytest.approx(b["total_ms"], abs=1.5)

    def test_program_inventory_covers_draft_site(self):
        net = _net()
        twin = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=1, page_size=8, pages_per_slot=3,
            prefill_chunk=8, spec=SpecConfig(draft_model=twin, k=2)))
        eng.submit(np.arange(8, dtype=np.int32) % 128, 6)
        eng.run()
        inv = eng.record_program_stats()
        assert set(inv) == set(eng.compiled_sites)
        assert len(inv) == 2


class TestSpecConfigValidation:
    def test_rejects_mismatches(self):
        net = _net()
        twin = _net()
        base = dict(num_slots=1, page_size=8, pages_per_slot=2)
        # decode="sampling" is SUPPORTED since ISSUE 20 (rejection
        # sampling); what still raises is overlap without sampling —
        # greedy has no chained draft build to hide the sync under
        with pytest.raises(ValueError):
            ServingEngine(net, ServingConfig(
                decode="greedy",
                spec=SpecConfig(draft_model=twin, k=2, overlap=True),
                **base))
        with pytest.raises(ValueError):
            ServingEngine(net, ServingConfig(
                spec=SpecConfig(draft_model=twin, k=0), **base))
        paddle.seed(1)
        other_vocab = GPT(GPTConfig(vocab_size=64, hidden_size=32,
                                    num_layers=1, num_heads=2,
                                    max_seq_len=64))
        other_vocab.eval()
        with pytest.raises(ValueError):
            ServingEngine(net, ServingConfig(
                spec=SpecConfig(draft_model=other_vocab, k=2), **base))
        paddle.seed(2)
        short_ctx = GPT(GPTConfig(vocab_size=128, hidden_size=32,
                                  num_layers=1, num_heads=2,
                                  max_seq_len=16))
        short_ctx.eval()
        with pytest.raises(ValueError):
            ServingEngine(net, ServingConfig(
                spec=SpecConfig(draft_model=short_ctx, k=2), **base))


@pytest.mark.slow
class TestSpecWorkload:
    def test_spec_poisson_amortizes_ticks(self):
        """The throughput mechanism, asserted on counters (CPU wall
        clocks are noisy; the serve_bench --spec-decode JSON carries
        the timed comparison): on a Poisson trace with a twin draft,
        the spec engine emits strictly more than one token per verify
        tick on average, accepts most drafts, and stays bitwise equal
        to the plain engine."""
        import importlib.util
        import os

        from paddle_tpu.profiler import registry

        spec_mod = importlib.util.spec_from_file_location(
            "serve_bench", os.path.join(os.path.dirname(__file__),
                                        os.pardir, "benchmarks",
                                        "serve_bench.py"))
        sb = importlib.util.module_from_spec(spec_mod)
        spec_mod.loader.exec_module(sb)

        net = _net()
        twin = _net()
        trace = sb.make_trace(10, (8, 16), 24, 1000.0)
        cfgkw = dict(num_slots=4, page_size=8, pages_per_slot=5,
                     prefill_chunk=8)
        plain = ServingEngine(net, ServingConfig(**cfgkw))
        spec = ServingEngine(net, ServingConfig(
            spec=SpecConfig(draft_model=twin, k=4), **cfgkw))
        t0 = registry().counter("serving/ticks").value
        sb.run_engine(plain, trace)
        plain_ticks = registry().counter("serving/ticks").value - t0
        t0 = registry().counter("serving/ticks").value
        g0 = registry().counter("serving/tokens_generated").value
        sb.run_engine(spec, trace)
        spec_ticks = registry().counter("serving/ticks").value - t0
        gen = registry().counter("serving/tokens_generated").value - g0
        p_res = {r.prompt.tobytes(): r.out
                 for r in plain._requests.values() if r.done}
        s_res = {r.prompt.tobytes(): r.out
                 for r in spec._requests.values() if r.done}
        assert p_res == s_res                     # bitwise engine parity
        assert gen / spec_ticks > 1.3             # amortization happened
        assert spec_ticks < plain_ticks
        rate = registry().gauge("serving/spec_accept_rate").value
        assert rate > 0.7
