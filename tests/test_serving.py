"""paddle_tpu.serving: paged KV cache + continuous-batching engine +
prefix caching.

The load-bearing contract is BITWISE greedy parity with the dense-cache
``generate()``: the paged engine runs the same compiled math (same
contraction order, same reduction lengths) whenever the slot capacity
equals the dense path's prompt+max_new — and prefix caching must
preserve it exactly (aliased pages hold identical KV by construction),
so every cached-engine output is pinned against both the uncached
engine and the dense path. Every parity test here uses a model/seed
whose greedy output is VARIED (a collapsed argmax sequence would hide
KV-placement bugs).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import gpt_tiny
from paddle_tpu.ops import decoding as D
from paddle_tpu.serving import (NULL_PAGE, PageAllocator, PagePool,
                                PrefixCache, ServingConfig, ServingEngine)

pytestmark = pytest.mark.serving


def _net(seed=0):
    """initializer_range=0.2 makes tiny-GPT greedy decode context-
    dependent (the default 0.02 collapses to one repeated argmax token,
    which would let cache bugs pass parity)."""
    paddle.seed(seed)
    net = gpt_tiny(initializer_range=0.2)
    net.eval()
    return net


def _dense(net, prompt, max_new, **kw):
    ids, _ = net.generate(paddle.to_tensor(prompt[None]),
                          max_new_tokens=max_new, **kw)
    return ids.numpy()[0]


class TestPageAllocator:
    def test_alloc_free_and_null_page_guard(self):
        a = PageAllocator(5)
        assert a.num_free == 4           # page 0 reserved
        got = a.alloc(3)
        assert len(got) == 3 and NULL_PAGE not in got
        assert a.alloc(2) is None        # all-or-nothing
        assert a.num_free == 1           # failed alloc left state alone
        a.free(got)
        assert a.num_free == 4
        with pytest.raises(ValueError):
            a.free([NULL_PAGE])
        with pytest.raises(ValueError):
            a.free([got[0], got[0]])     # double free

    def test_utilization(self):
        a = PageAllocator(5)
        a.alloc(2)
        assert a.utilization() == 0.5

    def test_refcount_share_and_staged_release(self):
        """share -> first holder releases -> page survives -> last
        release frees; over-freeing raises."""
        a = PageAllocator(6)
        got = a.alloc(2)
        assert all(a.refcount(p) == 1 for p in got)
        a.share([got[0]])
        assert a.refcount(got[0]) == 2
        a.free(got)                      # first holder lets go of both
        assert a.refcount(got[0]) == 1   # still held by the sharer
        assert a.refcount(got[1]) == 0
        assert a.num_free == 4
        a.free([got[0]])                 # last reference
        assert a.num_free == 5
        with pytest.raises(ValueError):
            a.free([got[0]])
        with pytest.raises(ValueError):
            a.share([got[1]])            # unallocated


class TestPrefixCacheUnit:
    def _pool(self, **kw):
        kw.setdefault("num_layers", 1)
        kw.setdefault("num_pages", 8)
        kw.setdefault("page_size", 4)
        kw.setdefault("num_heads", 1)
        kw.setdefault("head_dim", 2)
        kw.setdefault("num_slots", 2)
        kw.setdefault("pages_per_slot", 3)
        kw.setdefault("prefix_cache", True)
        return PagePool(**kw)

    def test_insert_lookup_and_lifecycle(self):
        """Indexed pages survive their slot's release (the index holds a
        refcount) and only pressure-eviction of UNREFERENCED pages frees
        them — share -> evict attempt -> survives -> release -> freed."""
        pool = self._pool()
        toks = np.arange(8, dtype=np.int32)
        assert pool.grow_slot(0, 2)
        pages = [int(p) for p in pool.tables[0, :2]]
        assert pool.prefix.insert(toks, pages) == 2
        assert pool.prefix.insert(toks, pages) == 0   # idempotent
        assert len(pool.prefix) == 2
        pool.release_slot(0)
        assert pool.allocator.num_allocated == 2      # index kept them
        # a new sharer aliases the chain (lookup caps at len-1: 9-token
        # prompt -> both 4-token chunks usable)
        query = np.concatenate([toks, [99]]).astype(np.int32)
        full, partial = pool.prefix.lookup(query)
        assert full == pages and partial is None
        pool.share_into_slot(1, full)
        assert pool.prefix.evict_for(2) == 0          # refcount 2: pinned
        assert pool.allocator.num_allocated == 2
        pool.release_slot(1)
        assert pool.prefix.evict_for(2) == 2          # now unreferenced
        assert pool.allocator.num_allocated == 0
        assert len(pool.prefix) == 0

    def test_partial_chunk_lookup_reports_lcp(self):
        pool = self._pool()
        toks = np.arange(8, dtype=np.int32)
        pool.grow_slot(0, 2)
        pages = [int(p) for p in pool.tables[0, :2]]
        pool.prefix.insert(toks, pages)
        # diverges inside the second chunk after 2 agreeing tokens
        q = np.array([0, 1, 2, 3, 4, 5, 90, 91, 92], np.int32)
        full, partial = pool.prefix.lookup(q)
        assert full == [pages[0]]
        assert partial == (pages[1], 2)
        # lookup is capped at len-1 even on a full-chain match
        full, partial = pool.prefix.lookup(toks)
        assert full == [pages[0]] and partial == (pages[1], 3)

    def test_release_slot_idempotent_under_refcounts(self):
        """engine._finish and preemption can both reach release_slot;
        the second call must be a clean no-op while a genuine double
        free of a page still raises inside the allocator."""
        pool = self._pool(prefix_cache=False)
        pool.grow_slot(0, 2)
        held = list(pool._held[0])
        assert pool.release_slot(0) == 2
        assert pool.release_slot(0) == 0              # idempotent
        assert (pool.tables[0] == NULL_PAGE).all()
        with pytest.raises(ValueError):
            pool.allocator.free(held)                 # already freed

    def test_lru_evicts_leaf_first(self):
        pool = self._pool()
        a = np.arange(8, dtype=np.int32)
        pool.grow_slot(0, 2)
        pages = [int(p) for p in pool.tables[0, :2]]
        pool.prefix.insert(a, pages)
        pool.release_slot(0)
        assert pool.prefix.evict_for(1) == 1
        # the LEAF (second chunk) went first: the root chunk still hits
        full, _ = pool.prefix.lookup(np.concatenate([a[:4], [7]])
                                     .astype(np.int32))
        assert full == [pages[0]]


class TestPagedParity:
    def test_mixed_lengths_slot_reuse_bitwise(self):
        """Five mixed-length requests through TWO slots: continuous
        admission, slot reuse, chunked prefill at both lengths — every
        output bitwise equal to its own dense generate(). Also pins the
        dispatch-site contract of the unified engine: ONE compiled
        hot-path program (the mixed-row tick) that traces exactly ONCE
        — there is no separate ``serving.prefill`` program anymore, and
        any regression re-growing a dispatch site or retracing the tick
        fails here."""
        import paddle_tpu.profiler as profiler
        from paddle_tpu.profiler import recompile

        net = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=3, num_pages=7,
            prefill_chunk=8))
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 128, (t,)).astype(np.int32)
                   for t in (8, 16, 8, 16, 8)]
        profiler.enable()
        rids = [eng.submit(p, 24 - len(p)) for p in prompts]
        out = eng.run()
        profiler.disable()
        for p, rid in zip(prompts, rids):
            want = _dense(net, p, 24 - len(p))
            assert len(set(want.tolist())) >= 4   # varied => real signal
            np.testing.assert_array_equal(out[rid], want)
        counts = recompile.trace_counts()
        assert eng.compiled_sites == (eng._tick_site,)   # ONE site
        assert counts[eng._tick_site] == 1               # ONE trace
        retraces = [r for r in recompile.retraces()
                    if r["site"].startswith("serving.")]
        assert not retraces
        # deferred sync actually deferred something
        assert eng.max_inflight_seen >= 2

    def test_generate_paged_wrapper_bitwise(self):
        net = _net()
        toks = np.random.RandomState(0).randint(0, 128, (2, 12)) \
            .astype(np.int32)
        dense, _ = net.generate(paddle.to_tensor(toks), max_new_tokens=12)
        paged, _ = net.generate(paddle.to_tensor(toks), max_new_tokens=12,
                                paged=True, page_size=8)
        np.testing.assert_array_equal(dense.numpy(), paged.numpy())

    def test_eos_matches_dense_freeze(self):
        """Dense path freezes finished rows to EOS; the engine evicts and
        the wrapper pads — the observable [B, max_new] ids must match."""
        net = _net()
        toks = np.random.RandomState(5).randint(0, 128, (2, 6)) \
            .astype(np.int32)
        first, _ = net.generate(paddle.to_tensor(toks), max_new_tokens=2)
        eos = int(first.numpy()[0, 1])
        dense, _ = net.generate(paddle.to_tensor(toks), max_new_tokens=10,
                                eos_token_id=eos)
        paged, _ = net.generate(paddle.to_tensor(toks), max_new_tokens=10,
                                eos_token_id=eos, paged=True, page_size=8)
        np.testing.assert_array_equal(dense.numpy(), paged.numpy())

    def test_sampling_reproducible_and_topk1_is_greedy(self):
        net = _net()
        toks = np.random.RandomState(1).randint(0, 128, (2, 8)) \
            .astype(np.int32)
        a, _ = net.generate(paddle.to_tensor(toks), max_new_tokens=8,
                            decode_strategy="sampling", top_k=8, seed=5,
                            paged=True)
        b, _ = net.generate(paddle.to_tensor(toks), max_new_tokens=8,
                            decode_strategy="sampling", top_k=8, seed=5,
                            paged=True)
        np.testing.assert_array_equal(a.numpy(), b.numpy())
        g, _ = net.generate(paddle.to_tensor(toks), max_new_tokens=8)
        s1, _ = net.generate(paddle.to_tensor(toks), max_new_tokens=8,
                             decode_strategy="sampling", top_k=1, seed=9,
                             paged=True)
        np.testing.assert_array_equal(g.numpy(), s1.numpy())


class TestPrefixCaching:
    def test_cached_vs_uncached_bitwise_across_admission_orders(self):
        """THE prefix-cache parity contract: greedy decode with the
        cache on is bitwise identical to the cache-off engine (and to
        dense generate()) for every request, regardless of admission
        order — aliased pages hold identical KV by construction and
        reduction lengths never change. Shared 16-token system prompt,
        unique suffixes, two slots (so admission interleaves with
        running decodes)."""
        from paddle_tpu.profiler import registry

        net = _net()
        rng = np.random.RandomState(9)
        system = rng.randint(0, 128, (16,)).astype(np.int32)
        prompts = [np.concatenate(
            [system, rng.randint(0, 128, (8,)).astype(np.int32)])
            for _ in range(4)]
        cfgkw = dict(num_slots=2, page_size=8, pages_per_slot=5,
                     prefill_chunk=8)
        dense_out = {i: _dense(net, p, 8) for i, p in enumerate(prompts)}

        hits0 = registry().counter("serving/prefix_hit_tokens").value
        for order in (range(4), reversed(range(4))):
            order = list(order)
            on = ServingEngine(net, ServingConfig(
                prefix_cache=True, **cfgkw))
            off = ServingEngine(net, ServingConfig(
                prefix_cache=False, **cfgkw))
            on_rids = {i: on.submit(prompts[i], 8) for i in order}
            off_rids = {i: off.submit(prompts[i], 8) for i in order}
            on_out, off_out = on.run(), off.run()
            for i in order:
                np.testing.assert_array_equal(on_out[on_rids[i]],
                                              off_out[off_rids[i]])
                np.testing.assert_array_equal(on_out[on_rids[i]],
                                              dense_out[i])
        hits = registry().counter("serving/prefix_hit_tokens").value
        assert hits > hits0                  # sharing actually happened
        assert registry().counter("serving/prefix_lookups").value > 0

    def test_preempt_requeue_reuses_own_prefix(self):
        """Pool smaller than full residency: the engine preempts
        (requeue with generated prefix) instead of deadlocking, the
        victim's fully-written pages enter the prefix index first, and
        its re-admission aliases them — so preemption stops redoing
        work. Results stay bitwise equal to the dense path."""
        from paddle_tpu.profiler import registry

        net = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=3, num_pages=5,
            prefill_chunk=8))
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 128, (8,)).astype(np.int32)
                   for _ in range(3)]
        pre0 = registry().counter("serving/preemptions").value
        hit0 = registry().counter("serving/prefix_hit_tokens").value
        rids = [eng.submit(p, 16) for p in prompts]
        out = eng.run()
        assert registry().counter("serving/preemptions").value > pre0
        # the requeued victims re-aliased their own cached pages
        assert registry().counter("serving/prefix_hit_tokens").value > hit0
        for p, rid in zip(prompts, rids):
            np.testing.assert_array_equal(out[rid], _dense(net, p, 16))
        eng.pool.drop_prefix_cache()
        assert eng.pool.allocator.num_allocated == 0

    def test_event_timeline_and_requeue_wait_under_preemption(self):
        """ISSUE 8: per-request event timelines under preempt-requeue —
        (a) ordering invariants submit <= admit <= first_token <=
        finish per request, with preempt -> requeue -> re-admit in
        order; (b) the latency breakdown charges preempted time to its
        own bucket; (c) regression: a preempt->requeue cycle lands in
        serving/requeue_wait_ms, NOT back in the submit-anchored
        serving/prefill_queue_wait_ms (which previously conflated
        scheduler delay with preemption cost)."""
        from paddle_tpu.profiler import (event_log, latency_breakdown,
                                         registry)

        net = _net()
        # pool smaller than residency: preemption guaranteed (same
        # shape as test_preempt_requeue_reuses_own_prefix)
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=3, num_pages=5,
            prefill_chunk=8))
        qw0 = registry().histogram("serving/prefill_queue_wait_ms").count
        rw0 = registry().histogram("serving/requeue_wait_ms").count
        pre0 = registry().counter("serving/preemptions").value
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 128, (8,)).astype(np.int32)
                   for _ in range(3)]
        rids = [eng.submit(p, 16) for p in prompts]
        eng.run()
        preempts = registry().counter("serving/preemptions").value - pre0
        assert preempts > 0

        def mine(rid):
            return [e for e in event_log().events(rid=rid)
                    if e.attrs.get("eng") == eng._eng_id]

        preempted_rids = 0
        for rid in rids:
            evs = mine(rid)
            first = {}
            for e in evs:
                first.setdefault(e.kind, e.t_ns)
            assert first["submit"] <= first["admit"] \
                <= first["first_token"] <= first["finish"]
            # every preempt is followed by a requeue then a re-admit
            kinds = [e.kind for e in evs]
            for i, k in enumerate(kinds):
                if k == "preempt":
                    assert "requeue" in kinds[i + 1:]
                    assert "admit" in kinds[i + 1:]
            b = latency_breakdown(rid)
            assert b["complete"] and b["tokens"] == 16
            if b["preempts"]:
                preempted_rids += 1
                assert b["preempted_ms"] > 0.0
        assert preempted_rids > 0
        # (c) the wait-accounting split: one submit-anchored wait per
        # FRESH admission, one requeue wait per preemption
        qw = registry().histogram("serving/prefill_queue_wait_ms").count
        rw = registry().histogram("serving/requeue_wait_ms").count
        assert qw - qw0 == len(rids)
        assert rw - rw0 == preempts

    def test_preempt_before_first_chunk_still_counts_fresh_wait(self):
        """An admission cycle preempted before it ever opened a prefill
        chunk must still record its wait sample at the preemption
        (previously lost: the one first-chunk-open observation then
        landed in requeue_wait_ms because preempts was already 1) — so
        qw == requests / rw == preemptions hold under EVERY
        interleaving, not just chunk-opens-before-preempt."""
        from paddle_tpu.profiler import registry

        net = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=4, num_pages=9,
            prefill_chunk=8, prefill_chunks_per_tick=1))
        qw0 = registry().histogram("serving/prefill_queue_wait_ms").count
        rw0 = registry().histogram("serving/requeue_wait_ms").count
        pre0 = registry().counter("serving/preemptions").value
        rng = np.random.RandomState(5)
        r0 = eng.submit(rng.randint(0, 128, (16,)).astype(np.int32), 8)
        eng.step()                  # r0 admitted, opens its first chunk
        r1 = eng.submit(rng.randint(0, 128, (8,)).astype(np.int32), 8)
        eng.step()                  # r1 admitted; chunk budget spent on r0
        s1 = eng._slot_rid.index(r1)
        assert not eng._slot_looked_up[s1]    # r1 never opened a chunk
        eng.drain(0)
        eng._preempt_for(eng._slot_rid.index(r0), 0)  # victim: youngest=r1
        assert eng._slot_rid[s1] is None
        out = eng.run()
        assert len(out[r1]) == 8              # r1 still completes
        assert registry().counter("serving/preemptions").value - pre0 == 1
        qw = registry().histogram("serving/prefill_queue_wait_ms").count
        rw = registry().histogram("serving/requeue_wait_ms").count
        assert qw - qw0 == 2                  # fresh sample NOT lost
        assert rw - rw0 == 1                  # one preemption, one requeue

    def test_cow_tail_page_isolation(self):
        """Two requests diverging MID-page: the second copy-on-writes
        the partially-agreeing tail page instead of aliasing it, so its
        divergent KV never corrupts the first tenant's cached page —
        both (and a re-run of the first) stay bitwise-dense."""
        from paddle_tpu.profiler import registry

        net = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=4,
            prefill_chunk=8))
        rng = np.random.RandomState(17)
        a = rng.randint(0, 128, (16,)).astype(np.int32)
        b = np.concatenate([a[:12],
                            (a[12:] + 1) % 128]).astype(np.int32)
        ra = eng.submit(a, 8)
        eng.run()
        cow0 = registry().counter("cache_share/cow_copies").value
        rb = eng.submit(b, 8)
        out_b = eng.run()[rb]
        assert registry().counter("cache_share/cow_copies").value > cow0
        np.testing.assert_array_equal(out_b, _dense(net, b, 8))
        # A's cached page survived B's divergent writes: resubmitting A
        # (now hitting its own chain, incl. another COW of the tail)
        ra2 = eng.submit(a, 8)
        out_a2 = eng.run()[ra2]
        np.testing.assert_array_equal(out_a2, _dense(net, a, 8))

    def test_exact_capacity_finish_publishes_clean_pages(self):
        """A request finishing at EXACT slot capacity keeps riding the
        fixed-shape tick (pos == cap) until its tokens drain; those
        out-of-range writes must land in the null page, NOT clamp into
        the slot's LAST page — _finish publishes that page into the
        prefix index, so a clamped write would poison every later
        prefix hit of the sequence."""
        net = _net()
        cfgkw = dict(num_slots=2, page_size=8, pages_per_slot=4,
                     prefill_chunk=8)
        rng = np.random.RandomState(31)
        a = rng.randint(0, 128, (9,)).astype(np.int32)
        b = rng.randint(0, 128, (8,)).astype(np.int32)
        noisy = ServingEngine(net, ServingConfig(**cfgkw))
        ra = noisy.submit(a, 24)      # 9 + 24 - 1 == 32 == capacity
        noisy.submit(b, 25)           # keeps ticking after A stops
        out_a = noisy.run()[ra]
        quiet = ServingEngine(net, ServingConfig(**cfgkw))
        ra2 = quiet.submit(a, 24)     # alone: no post-finish ticks
        np.testing.assert_array_equal(out_a, quiet.run()[ra2])
        seq = np.concatenate([a, out_a])[:26].astype(np.int32)
        pages = {}
        for name, eng in (("noisy", noisy), ("quiet", quiet)):
            full, partial = eng.pool.prefix.lookup(seq)
            assert len(full) == 3 and partial is not None
            pages[name] = np.asarray(eng.pool.k[:, partial[0]])
        # the published tail page (absolute positions 24..31, the write
        # target a clamped pos==32 would stomp at offset 0) is bitwise
        # identical with and without post-finish tick traffic
        np.testing.assert_array_equal(pages["noisy"], pages["quiet"])

    def test_chunked_prefill_does_not_block_decode(self):
        """Sarathi-style bound: a long prompt prefills one chunk per
        scheduler step, so an already-resident request keeps emitting
        tokens between chunks instead of stalling for the whole
        prompt."""
        from paddle_tpu.profiler import registry

        net = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=6,
            prefill_chunk=8, prefix_cache=False))
        rng = np.random.RandomState(23)
        short = rng.randint(0, 128, (8,)).astype(np.int32)
        long = rng.randint(0, 128, (40,)).astype(np.int32)
        r_short = eng.submit(short, 16)
        eng.step()                         # short fully prefilled
        chunks0 = registry().counter("serving/prefill_chunks").value
        r_long = eng.submit(long, 8)
        eng.step()                         # admit long + first chunk
        interleaved = 0
        mixed_ticks = 0
        while int(eng._slot_len[[s for s, r in enumerate(eng._slot_rid)
                                 if r == r_long][0]]) < 40:
            before = int(eng._slot_dispatched[
                [s for s, r in enumerate(eng._slot_rid)
                 if r == r_short][0]])
            eng.step()
            after = int(eng._slot_dispatched[
                [s for s, r in enumerate(eng._slot_rid)
                 if r == r_short][0]])
            interleaved += after - before
            # the unified tick carried BOTH kinds of rows in one
            # program: the mixed-row gauges are the direct evidence
            if registry().gauge("serving/mixed_rows_prefill").value and \
                    registry().gauge("serving/mixed_rows_decode").value:
                mixed_ticks += 1
        assert interleaved >= 3            # decode advanced per chunk
        assert mixed_ticks >= 3            # decode+prefill in ONE tick
        assert registry().gauge("serving/mixed_rows").value >= 1
        assert registry().counter("serving/prefill_chunks").value \
            - chunks0 == 5                 # 40 tokens / 8-token chunks
        out = eng.run()
        np.testing.assert_array_equal(out[r_short],
                                      _dense(net, short, 16))
        np.testing.assert_array_equal(out[r_long], _dense(net, long, 8))


class TestPerRequestSampling:
    def test_per_row_filter_matches_scalar(self):
        r = np.random.RandomState(0)
        logits = jnp.asarray(r.randn(4, 32).astype(np.float32))
        for tk, tp in ((0, 1.0), (5, 1.0), (0, 0.7), (8, 0.5),
                       (32, 1.0), (1, 0.0)):
            want = D.apply_top_k_top_p(logits, tk, tp)
            got = D.apply_top_k_top_p_per_row(
                logits, jnp.full((4,), tk, jnp.int32),
                jnp.full((4,), tp, jnp.float32))
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
        # mixed rows: each row equals its own scalar filtering
        tks = jnp.asarray([0, 3, 32, 1], jnp.int32)
        tps = jnp.asarray([1.0, 0.6, 0.9, 1.0], jnp.float32)
        got = D.apply_top_k_top_p_per_row(logits, tks, tps)
        for i in range(4):
            want = D.apply_top_k_top_p(logits[i:i + 1], int(tks[i]),
                                       float(tps[i]))
            np.testing.assert_array_equal(np.asarray(got[i]),
                                          np.asarray(want[0]))

    def test_per_request_overrides_reproducible_under_preemption(self):
        """Requests carry their own temperature/top_k/top_p through the
        fixed-shape tick: a top_k=1 request decodes greedily (== dense)
        even while its neighbour samples hot, and the whole mix is
        reproducible on a fresh engine under pool pressure (preemption
        requeues must not perturb anyone's stream)."""
        from paddle_tpu.profiler import recompile, registry

        net = _net()
        rng = np.random.RandomState(2)
        a = rng.randint(0, 128, (8,)).astype(np.int32)
        b = rng.randint(0, 128, (8,)).astype(np.int32)
        c = rng.randint(0, 128, (8,)).astype(np.int32)
        cfgkw = dict(num_slots=2, page_size=8, pages_per_slot=3,
                     num_pages=5, prefill_chunk=8, decode="sampling",
                     top_k=8, seed=5)

        def serve():
            eng = ServingEngine(net, ServingConfig(**cfgkw))
            rids = [eng.submit(a, 12, top_k=1),
                    eng.submit(b, 12, temperature=2.0, top_p=0.9),
                    eng.submit(c, 12)]
            out = eng.run()
            return eng, [out[r] for r in rids]

        pre0 = registry().counter("serving/preemptions").value
        eng1, outs1 = serve()
        assert registry().counter("serving/preemptions").value > pre0
        _, outs2 = serve()
        for o1, o2 in zip(outs1, outs2):
            np.testing.assert_array_equal(o1, o2)
        # the top_k=1 request is exactly greedy == dense
        np.testing.assert_array_equal(outs1[0], _dense(net, a, 12))
        # param variety rode the ONE compiled tick (no retraces)
        counts = recompile.trace_counts()
        tick = [k for k in counts if k.startswith("serving.tick")]
        assert all(counts[k] == 1 for k in tick)


class TestPageReuse:
    def test_no_cross_request_leakage(self):
        """Evicted pages are reused (LIFO free list hands the dirtiest
        page back first) WITHOUT leaking the previous tenant's KV: a
        request decoded on recycled pages equals the same request on a
        fresh engine, bitwise. With the prefix cache on, the first
        tenant's pages survive in the index until pool pressure evicts
        them — which this pool is sized to force."""
        net = _net()
        cfgkw = dict(num_slots=1, page_size=8, pages_per_slot=3,
                     num_pages=4, prefill_chunk=8)
        rng = np.random.RandomState(11)
        a = rng.randint(0, 128, (8,)).astype(np.int32)
        b = rng.randint(0, 128, (8,)).astype(np.int32)
        eng = ServingEngine(net, ServingConfig(**cfgkw))
        eng.submit(a, 16)
        eng.run()
        # a's full pages stay cached; b's growth must evict them
        assert eng.pool.allocator.num_allocated > 0
        rb = eng.submit(b, 16)                         # recycled pages
        out_b = eng.run()[rb]
        fresh = ServingEngine(net, ServingConfig(**cfgkw))
        rb2 = fresh.submit(b, 16)
        np.testing.assert_array_equal(out_b, fresh.run()[rb2])
        np.testing.assert_array_equal(out_b, _dense(net, b, 16))
        eng.pool.drop_prefix_cache()
        assert eng.pool.allocator.num_allocated == 0   # all refs settled


class TestPagedAttentionKernel:
    def test_pallas_kernel_matches_xla_reference(self):
        from paddle_tpu.ops.paged_attention import ragged_paged_attention

        B, NPs, P, ps, NH, Dh = 3, 4, 9, 8, 4, 16
        r = np.random.RandomState(0)
        kpool = jnp.asarray(r.randn(P, ps, NH, Dh).astype(np.float32))
        vpool = jnp.asarray(r.randn(P, ps, NH, Dh).astype(np.float32))
        q = jnp.asarray(r.randn(B, 1, NH, Dh).astype(np.float32))
        tab = jnp.asarray(r.randint(1, P, (B, NPs)).astype(np.int32))
        pos = jnp.asarray(np.array([5, 17, 30], np.int32))
        one = jnp.ones((B,), jnp.int32)       # decode rows
        ref = ragged_paged_attention(q, kpool, vpool, tab, pos, one,
                                     impl="xla")
        ker = ragged_paged_attention(q, kpool, vpool, tab, pos, one,
                                     impl="pallas")
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_chunk_row_of_length_1_matches_decode_row(self):
        """A chunk row one query wide is the decode row at the same
        position (same gather, same mask, same reduction), alone or
        beside other rows: the reads agree exactly."""
        from paddle_tpu.ops.paged_attention import ragged_paged_attention

        r = np.random.RandomState(1)
        kpool = jnp.asarray(r.randn(6, 8, 4, 16).astype(np.float32))
        vpool = jnp.asarray(r.randn(6, 8, 4, 16).astype(np.float32))
        q = jnp.asarray(r.randn(3, 1, 4, 16).astype(np.float32))
        tab = jnp.asarray(np.array([[2, 5, 1], [3, 4, 0], [1, 2, 5]],
                                   np.int32))
        pos = jnp.asarray(np.array([13, 9, 20], np.int32))
        dec = ragged_paged_attention(q, kpool, vpool, tab, pos,
                                     jnp.ones((3,), jnp.int32))
        # as the chunk group spells its metadata, one row at a time
        for i in range(3):
            pre = ragged_paged_attention(
                q[i:i + 1], kpool, vpool, tab[i:i + 1],
                jnp.broadcast_to(pos[i], (1,)),
                jnp.full((1,), q.shape[1], jnp.int32))
            np.testing.assert_array_equal(np.asarray(dec)[i:i + 1],
                                          np.asarray(pre))

    def test_unknown_impl_raises(self):
        from paddle_tpu.ops.paged_attention import ragged_paged_attention

        with pytest.raises(ValueError):
            ragged_paged_attention(None, None, None, None, None, None,
                                   impl="cuda")


class TestRaggedAttention:
    """ops/paged_attention.ragged_paged_attention — the ONE attention
    entry point over per-row (pos0, true_len) metadata that serves
    decode rows (true_len == 1) and prefill-chunk rows in the same
    call (and, on the Pallas path, the same grid)."""

    def _pools(self, seed=0, pages=9, ps=8, nh=4, hd=16):
        r = np.random.RandomState(seed)
        k = jnp.asarray(r.randn(pages, ps, nh, hd).astype(np.float32))
        v = jnp.asarray(r.randn(pages, ps, nh, hd).astype(np.float32))
        return r, k, v

    def test_pallas_matches_xla_mixed_rows(self):
        """Interpret-mode Pallas vs XLA allclose over one metadata
        matrix mixing every serving row kind: decode rows at position
        0 / mid-page / page boundary / exact slot capacity, rows whose
        tables hold NULL pages (partially-grown slots), rows ALIASING
        the same physical pages (prefix sharing + COW donors), and the
        null-page-routed write target of the exact-capacity regression
        (pos == cap reads only masked garbage)."""
        from paddle_tpu.ops.paged_attention import ragged_paged_attention

        r, kpool, vpool = self._pools(seed=3)
        tab = jnp.asarray(np.array([
            [3, 0, 0, 0],      # one-page slot: three null entries
            [3, 5, 0, 0],      # aliases row 0's page (prefix share)
            [3, 5, 7, 2],      # fully grown, same prefix chain
            [8, 0, 0, 0],      # COW'd divergent tail page
        ], np.int32))
        pos0 = jnp.asarray(np.array([0, 9, 31, 7], np.int32))
        tl = jnp.ones((4,), jnp.int32)
        q = jnp.asarray(r.randn(4, 1, 4, 16).astype(np.float32))
        ref = ragged_paged_attention(q, kpool, vpool, tab, pos0, tl)
        ker = ragged_paged_attention(q, kpool, vpool, tab, pos0, tl,
                                     impl="pallas")
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_pallas_matches_xla_ragged_chunk_rows(self):
        """Chunk-width rows with RAGGED true_len: the kernel skips
        fully-masked page blocks per row (its block-skip predicate is
        pos0 + true_len - 1), so only the real queries — i < true_len —
        are comparable; pad queries are explicitly garbage on both
        paths."""
        from paddle_tpu.ops.paged_attention import ragged_paged_attention

        r, kpool, vpool = self._pools(seed=5)
        tab = jnp.asarray(np.array([[3, 5, 7, 2],
                                    [3, 5, 0, 0],
                                    [6, 1, 4, 0]], np.int32))
        pos0 = jnp.asarray(np.array([8, 8, 0], np.int32))
        tl = jnp.asarray(np.array([8, 5, 1], np.int32))   # ragged
        q = jnp.asarray(r.randn(3, 8, 4, 16).astype(np.float32))
        ref = np.asarray(ragged_paged_attention(
            q, kpool, vpool, tab, pos0, tl))
        ker = np.asarray(ragged_paged_attention(
            q, kpool, vpool, tab, pos0, tl, impl="pallas"))
        for row, n in enumerate(np.asarray(tl)):
            np.testing.assert_allclose(ker[row, :n], ref[row, :n],
                                       rtol=2e-5, atol=2e-5)


class TestUnifiedTick:
    def test_program_inventory_covers_every_dispatched_site(self):
        """ISSUE 8 regression: record_program_stats() must return one
        inventory entry per compiled_sites program that dispatched —
        the avals are captured at first dispatch, and losing that
        capture silently empties the xla_programs bench block (the
        sink-schema CI leg caught exactly that)."""
        net = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=3,
            prefill_chunk=8))
        rid = eng.submit(np.arange(8, dtype=np.int32) % 128, 4)
        eng.run()
        inv = eng.record_program_stats()
        assert set(inv) == set(eng.compiled_sites)
        for site, rec in inv.items():
            assert rec["site"] == site
            assert rec["compile_ms"] > 0.0
            assert {"flops", "bytes_accessed", "cost_available"} \
                <= set(rec)

    def test_kernel_selection(self, attention_spelling):
        """The tick's attention is picked where the tick is traced
        (``paged_attention.resolve_impl``) and nowhere else: what an
        engine's tick took is what ``serving/attn_calls{path=}`` counted
        while it was traced."""
        from paddle_tpu.profiler import registry

        net = _net()
        cfgkw = dict(num_slots=1, page_size=8, pages_per_slot=2)

        def took():
            calls = {p: registry().counter("serving/attn_calls{path=%s}" % p)
                     for p in ("xla", "pallas")}
            before = {p: c.value for p, c in calls.items()}
            eng = ServingEngine(net, ServingConfig(**cfgkw))
            eng.submit(np.arange(5, dtype=np.int32), 2)
            eng.run()
            return {p: c.value - before[p] for p, c in calls.items()}

        here = took()                       # the CPU: the XLA spelling
        assert here["xla"] > 0 and here["pallas"] == 0
        attention_spelling("pallas")
        kernel = took()
        assert kernel["pallas"] == here["xla"] and kernel["xla"] == 0
        # no configuration names a kernel: the option (PR 28's
        # ``attention_kernel``, and ``attention_impl`` before it) is gone
        for gone in ("attention_kernel", "attention_impl"):
            with pytest.raises(TypeError, match=gone):
                ServingConfig(**{gone: "ragged-pallas"}, **cfgkw)
        assert not hasattr(ServingEngine, "attention_kernel")


@pytest.mark.slow
class TestRaggedPallasEngine:
    def test_pallas_engine_greedy_matches_xla_engine(self,
                                                     attention_spelling):
        """The unified tick on the Pallas ragged kernel (interpret mode
        on CPU), end to end: mixed prefill/decode rows, slot reuse.
        Online softmax is allclose-not-bitwise vs the XLA gather, so
        greedy argmax agreement is pinned against the XLA ENGINE on
        this fixed seed (ties at float-ulp gaps would be a different
        token — deterministic here, and a mismatch would mean the
        kernel's numerics drifted beyond allclose)."""
        net = _net()
        cfgkw = dict(num_slots=2, page_size=8, pages_per_slot=3,
                     prefill_chunk=8)
        rng = np.random.RandomState(13)
        prompts = [rng.randint(0, 128, (8,)).astype(np.int32)
                   for _ in range(3)]
        xla = ServingEngine(net, ServingConfig(**cfgkw))
        x_rids = [xla.submit(p, 16) for p in prompts]
        x_out = xla.run()
        attention_spelling("pallas")        # ticks traced from here on
        pal = ServingEngine(net, ServingConfig(**cfgkw))
        p_rids = [pal.submit(p, 16) for p in prompts]
        p_out = pal.run()
        for pr, xr in zip(p_rids, x_rids):
            np.testing.assert_array_equal(p_out[pr], x_out[xr])


class TestServingPredictor:
    def test_predictor_surface_matches_dense(self):
        from paddle_tpu.inference import ServingPredictor

        net = _net()
        pred = ServingPredictor(net, max_new_tokens=16, num_slots=2,
                                page_size=8, pages_per_slot=3,
                                prefill_chunk=8)
        rng = np.random.RandomState(7)
        toks = rng.randint(0, 128, (2, 8)).astype(np.int32)
        out, lens = pred.run([toks])
        assert out.shape == (2, 16) and list(lens) == [16, 16]
        for i in range(2):
            np.testing.assert_array_equal(out[i],
                                          _dense(net, toks[i], 16))


class TestCacheCaps:
    def test_lru_cache_evicts_and_counts(self):
        from paddle_tpu.profiler import registry
        from paddle_tpu.utils.lru import LRUCache

        before = registry().counter("cache_evict/t").value
        c = LRUCache(2, "t")
        c["a"], c["b"] = 1, 2
        assert c.get("a") == 1       # refresh 'a'
        c["c"] = 3                   # evicts 'b' (LRU)
        assert "b" not in c and "a" in c and len(c) == 2
        assert c.evictions == 1
        assert registry().counter("cache_evict/t").value == before + 1
        evicted = []
        d = LRUCache(1, "t", on_evict=lambda k, v: evicted.append(k))
        d["x"], d["y"] = 1, 2
        assert evicted == ["x"]

    def test_gen_jit_cache_capped(self, monkeypatch):
        from paddle_tpu.models.gpt import GPT

        monkeypatch.setattr(GPT, "GEN_JIT_CACHE_SIZE", 2)
        net = _net()
        toks = np.random.RandomState(0).randint(0, 128, (1, 6)) \
            .astype(np.int32)
        for n in (1, 2, 3):
            net.generate(paddle.to_tensor(toks), max_new_tokens=n)
        cache = net.__dict__["_gen_jit"]
        assert len(cache) == 2 and cache.evictions >= 1

    def test_predictor_bucket_exec_is_lru(self):
        from paddle_tpu.inference import Predictor
        from paddle_tpu.utils.lru import LRUCache

        # class-level contract check (loading real artifacts is covered
        # by test_inference.py): the bucket-executable cache is the
        # LRU-capped type with the companion jit-wrapper eviction hook
        p = Predictor.__new__(Predictor)
        p._jit_calls = {}
        p._bucket_exec = LRUCache(
            Predictor.BUCKET_EXEC_CACHE_SIZE, "predictor_exec",
            on_evict=lambda _b, exe: p._jit_calls.pop(id(exe), None))
        assert Predictor.BUCKET_EXEC_CACHE_SIZE >= 1
        sentinel = object()
        p._jit_calls[id(sentinel)] = "wrapped"
        p._bucket_exec[4] = sentinel
        for b in range(Predictor.BUCKET_EXEC_CACHE_SIZE):
            p._bucket_exec[100 + b] = object()
        assert 4 not in p._bucket_exec
        assert id(sentinel) not in p._jit_calls   # evicted together


@pytest.mark.slow
class TestPoissonThroughput:
    def test_continuous_batching_beats_sequential(self):
        """Poisson arrivals, >= 8 concurrent, mixed prompt lengths: the
        engine must out-serve sequential per-request generate(). The
        committed bench (BENCH_SERVE_r06.json) measured 6.5x on the
        whole-prompt-prefill design and 5.8x with chunked prefill
        (BENCH_SERVE_r07.json notes the trade: bounded decode stalls);
        this in-suite check uses a mid-size model and a lenient bar so
        CI boxes of any speed pass deterministically."""
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "serve_bench", os.path.join(os.path.dirname(__file__),
                                        os.pardir, "benchmarks",
                                        "serve_bench.py"))
        sb = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sb)

        paddle.seed(0)
        from paddle_tpu.models import GPT, GPTConfig

        net = GPT(GPTConfig(vocab_size=256, hidden_size=192,
                            num_layers=4, num_heads=4, max_seq_len=128,
                            initializer_range=0.2))
        net.eval()
        prompt_lens, max_new, slots = (8, 16, 32), 24, 8
        cap = (max(prompt_lens) + max_new + 15) // 16
        trace = sb.make_trace(16, prompt_lens, max_new, 1000.0)
        for t0 in prompt_lens:
            net.generate(paddle.to_tensor(
                np.zeros((1, t0), np.int32)), max_new_tokens=max_new)
        eng = sb.build_engine(net, slots, 16, cap)
        sb.run_engine(eng, [(0.0, p, m) for _, p, m in trace[:slots]])
        bl_tokens, bl_wall, _ = sb.run_baseline(net, trace)
        eng_tokens, eng_wall, _, occ, _ = sb.run_engine(eng, trace)
        assert eng_tokens == bl_tokens
        assert max(occ) >= 8          # actually reached 8 concurrent
        speedup = (eng_tokens / eng_wall) / (bl_tokens / bl_wall)
        assert speedup >= 1.5, f"continuous batching speedup {speedup}"

    def test_shared_prefix_poisson_workload(self):
        """The heavy prefix workload: Poisson arrivals where every
        prompt shares a system prefix — cache-on must beat cache-off on
        mean TTFT (lenient bar; the committed BENCH_SERVE_r07.json
        measures ~2x on the full config)."""
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "serve_bench", os.path.join(os.path.dirname(__file__),
                                        os.pardir, "benchmarks",
                                        "serve_bench.py"))
        sb = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sb)

        paddle.seed(0)
        from paddle_tpu.models import GPT, GPTConfig

        net = GPT(GPTConfig(vocab_size=256, hidden_size=192,
                            num_layers=4, num_heads=4, max_seq_len=256,
                            initializer_range=0.2))
        net.eval()
        reqs = sb.make_shared_prefix_requests(8, 64, 8, 16)
        means = {}
        for cached in (False, True):
            eng = sb.build_engine(net, 8, 16, 6, prefill_chunk=32,
                                  prefix_cache=cached)
            sb.run_concurrent(eng, reqs)       # warm
            eng.pool.drop_prefix_cache()
            eng.reset_results()
            _, _, ttfts = sb.run_concurrent(eng, reqs)
            means[cached] = float(np.mean(ttfts))
        assert means[False] / means[True] >= 1.2, means
