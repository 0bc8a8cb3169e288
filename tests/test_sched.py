"""SLO-aware serving scheduler (serving/sched.py, ISSUE 15).

Three contracts pinned here:

1. **Policies are host-side only.** Under EVERY chunk-selection policy
   the engine keeps exactly its usual compiled sites, each tracing
   once, and per-request greedy output stays BITWISE equal to dense
   ``generate()`` (fifo/sjf keep the full parity pin; aged-sjf pins
   per-request equality with the interleaving free to differ — which
   is all it ever changes).
2. **aged-sjf is starvation-free with a PROVABLE bound**: under a
   hostile short-prompt flood a long prompt opens its first chunk
   within ``ChunkScheduler.starvation_bound_ticks()`` scheduler ticks
   (and pure SJF, run on the same flood, demonstrably waits longer —
   the pathology aging exists to bound).
3. **Adaptive spec-k converges at both accept-rate extremes**: a twin
   draft keeps every slot at full depth; an independent draft decays
   to depth 0, after which the engine stops paying ANY draft cost
   (draft ticks stop dispatching) while output stays bitwise the
   plain engine's.

Engine tests stay lean (the tier-1 cap is saturated); the measured
tokens/s comparisons live in serve_bench --sched-matrix /
--adaptive-k (BENCH_SERVE_r15.json) and the CI serve-smoke leg.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPT, GPTConfig, gpt_tiny
from paddle_tpu.serving import (SCHED_POLICIES, ChunkScheduler,
                                ServingConfig, ServingEngine,
                                SpecConfig, SpecKController)
from paddle_tpu.serving.sched import ttfc_key

pytestmark = pytest.mark.serving


def _net(seed=0):
    """initializer_range=0.2: varied greedy output (test_serving rule —
    a collapsed argmax sequence would hide scheduling bugs too)."""
    paddle.seed(seed)
    net = gpt_tiny(initializer_range=0.2)
    net.eval()
    return net


def _dense(net, prompt, max_new, **kw):
    ids, _ = net.generate(paddle.to_tensor(prompt[None]),
                          max_new_tokens=max_new, **kw)
    return ids.numpy()[0]


def _prompts(lens, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 128, (t,)).astype(np.int32) for t in lens]


# ---------------------------------------------------------------------------
# ChunkScheduler unit
# ---------------------------------------------------------------------------
class TestChunkSchedulerUnit:
    def _sched(self, policy, ns=4, cap=64, chunk=8, npf=2, rate=None):
        return ChunkScheduler(policy, ns, cap, chunk, npf,
                              age_rate_tokens=rate)

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError, match="unknown scheduler"):
            self._sched("lifo")

    def test_fifo_ignores_remaining(self):
        s = self._sched("fifo")
        # (slot, admit_seq, remaining): oldest admission wins even
        # with the largest remaining prefill — the pre-ISSUE-15 order
        assert s.pick([(0, 5, 100), (1, 9, 1), (2, 7, 50)]) == 0
        assert s.pick([]) is None

    def test_sjf_orders_by_remaining_with_fifo_tiebreak(self):
        s = self._sched("sjf")
        assert s.pick([(0, 5, 100), (1, 9, 1), (2, 7, 50)]) == 1
        # tie on remaining -> oldest admission
        assert s.pick([(0, 9, 8), (1, 5, 8)]) == 1

    def test_aged_sjf_promotes_and_counts(self):
        from paddle_tpu.profiler import registry

        s = self._sched("aged-sjf", cap=64, chunk=8, rate=8)
        s.note_admit(0)
        c0 = registry().counter("serving/aged_promotions").value
        # fresh: pure SJF order (no promotion counted)
        assert s.pick([(0, 1, 64), (1, 2, 8)]) == 1
        assert registry().counter(
            "serving/aged_promotions").value == c0
        # slot 0 waits 8 ticks: 64 - 8*8 = 0 < 8 -> aged past the short
        for _ in range(8):
            s.on_tick()
        assert s.pick([(0, 1, 64), (1, 2, 8)]) == 0
        assert registry().counter(
            "serving/aged_promotions").value == c0 + 1
        # service resets the aging anchor: back to SJF order
        s.note_open(0)
        assert s.pick([(0, 1, 56), (1, 2, 8)]) == 1

    def test_aged_floor_ties_break_fifo(self):
        s = self._sched("aged-sjf", cap=16, chunk=8, rate=2)
        s.note_admit(0)
        s.note_admit(1)
        for _ in range(10):            # both priorities floor at 0
            s.on_tick()
        assert s.pick([(1, 9, 16), (0, 3, 16)]) == 0   # older seq

    def test_starvation_bound_formula(self):
        # default age_rate = chunk // 4 = 2:
        # ceil(72/2) + (3-1)*ceil(72/8) + 1
        s = self._sched("aged-sjf", ns=3, cap=72, chunk=8, npf=1)
        assert s.starvation_bound_ticks() == 36 + 18 + 1
        # explicit rate: one chunk of credit per tick
        s = self._sched("aged-sjf", ns=3, cap=72, chunk=8, npf=1,
                        rate=8)
        assert s.starvation_bound_ticks() == 9 + 18 + 1

    def test_first_open_wait_tracking(self):
        s = self._sched("aged-sjf")
        s.note_admit(2)
        for _ in range(5):
            s.on_tick()
        s.note_open(2)
        assert s.max_wait_ticks_seen == 5
        # later chunks of the same cycle don't re-record
        for _ in range(9):
            s.on_tick()
        s.note_open(2)
        assert s.max_wait_ticks_seen == 5
        # a released (preempted/finished) slot drops its latch
        s.note_admit(3)
        s.note_release(3)
        s.on_tick()
        s.note_open(3)
        assert s.max_wait_ticks_seen == 5

    def test_budget_fifo_is_constant(self):
        s = self._sched("fifo", npf=4)
        assert not s.shape_budget
        assert s.chunk_budget(3, 4, 0) == 4

    def test_budget_shaping_rules(self):
        s = self._sched("sjf", ns=4, npf=4)
        assert s.shape_budget
        # nothing pending: budget is irrelevant, full
        assert s.chunk_budget(0, 4, 0) == 4
        # decode-stall pressure: >= half the slots decoding, queue
        # empty -> halve
        assert s.chunk_budget(2, 2, 0) == 2
        # + rolling TPOT p95 risen >= 1.5x its own baseline -> floor 1
        s._tpot_ref, s._tpot_p95 = 10.0, 20.0
        assert s.chunk_budget(2, 2, 0) == 1
        # TTFT pressure buys the budget back: queue backlog...
        assert s.chunk_budget(2, 2, 3) == 4
        # ...or rolling TTFT p95 rising
        s._ttft_ref, s._ttft_p95 = 100.0, 200.0
        assert s.chunk_budget(2, 2, 0) == 4
        # light decode residency never cuts
        s._ttft_p95 = s._tpot_p95 = 0.0
        s._ttft_ref = s._tpot_ref = 0.0
        assert s.chunk_budget(2, 1, 0) == 4


class TestSpecKControllerUnit:
    def test_optimistic_start_and_extremes(self):
        c = SpecKController(2, 4)
        assert c.depth(0) == 4                  # full depth until data
        for _ in range(8):
            c.observe(0, 4, 4)                  # perfect acceptance
            c.observe(1, 0, 4)                  # total rejection
        assert c.depth(0) == 4 and c.ewma(0) == 1.0
        assert c.depth(1) == 0 and c.ewma(1) < 0.07
        # depth-0 slots produce no observations; reset re-arms
        c.reset(1)
        assert c.depth(1) == 4

    def test_intermediate_rate_maps_to_intermediate_depth(self):
        c = SpecKController(1, 4, ewma_alpha=1.0)   # no smoothing
        c.observe(0, 2, 4)
        assert c.depth(0) == 2
        c.observe(0, 1, 4)
        assert c.depth(0) == 1

    def test_zero_drafted_is_a_noop_and_alpha_validated(self):
        c = SpecKController(1, 4)
        c.observe(0, 0, 0)
        assert c.ewma(0) == 1.0
        with pytest.raises(ValueError):
            SpecKController(1, 4, ewma_alpha=0.0)


# ---------------------------------------------------------------------------
# engine-level: parity + single-trace under every policy
# ---------------------------------------------------------------------------
class TestPolicyParity:
    @pytest.mark.parametrize("policy", ["sjf", "aged-sjf"])
    def test_bitwise_parity_and_single_trace(self, policy):
        """Mixed-length requests, slot reuse, chunked prefill — every
        output bitwise equal to its own dense generate() under the
        non-default policies, with the ONE-site single-trace contract
        intact (the policy layer must never grow a dispatch site or
        retrace the tick). fifo's pin is the whole existing
        test_serving suite (its scheduling is bit-for-bit the old
        engine's)."""
        import paddle_tpu.profiler as profiler
        from paddle_tpu.profiler import recompile

        net = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=3, num_pages=7,
            prefill_chunk=8, prefill_chunks_per_tick=2,
            scheduler=policy))
        prompts = _prompts((8, 16, 8, 16))
        profiler.enable()
        rids = [eng.submit(p, 24 - len(p)) for p in prompts]
        out = eng.run()
        profiler.disable()
        for p, rid in zip(prompts, rids):
            want = _dense(net, p, 24 - len(p))
            assert len(set(want.tolist())) >= 4
            np.testing.assert_array_equal(out[rid], want)
        counts = recompile.trace_counts()
        assert eng.compiled_sites == (eng._tick_site,)
        assert counts[eng._tick_site] == 1
        assert not [r for r in recompile.retraces()
                    if r["site"].startswith("serving.")]

    def test_validation(self):
        net = _net()
        with pytest.raises(ValueError, match="unknown scheduler"):
            ServingEngine(net, ServingConfig(scheduler="lifo"))


# ---------------------------------------------------------------------------
# starvation freedom under a hostile flood
# ---------------------------------------------------------------------------
def _flood(policy, n_shorts=40):
    """One 64-token prompt admitted into a 3-slot engine, then a
    flood of 16-token single-emission shorts: with a 1-chunk budget
    and ``max_inflight=1`` (tight finish discovery -> fast slot
    recycling) some shorter request is pending nearly every tick, so
    pure SJF keeps passing the long over — the hostile regime the
    aging bound is stated against."""
    net = _net()
    eng = ServingEngine(net, ServingConfig(
        num_slots=3, page_size=8, pages_per_slot=9,
        prefill_chunk=8, max_inflight=1, scheduler=policy))
    prompts = _prompts([64] + [16] * n_shorts, seed=5)
    eng.submit(prompts[0], 4)
    for p in prompts[1:]:
        eng.submit(p, 1)
    out = eng.run()
    assert len(out) == 1 + n_shorts       # everybody finished
    return eng


class TestStarvationFreedom:
    def test_aged_sjf_bounds_the_long_prompts_wait(self):
        """THE aged-sjf invariant: every admitted request opens a
        chunk within ``starvation_bound_ticks()`` scheduler ticks,
        even under a continuous flood of shorter arrivals — the bound
        is derived in sched.py (priority floors after
        ceil(cap/age_rate) waited ticks; floor ties break FIFO) and
        asserted against the MEASURED worst wait."""
        from paddle_tpu.profiler import registry

        p0 = registry().counter("serving/aged_promotions").value
        eng = _flood("aged-sjf")
        bound = eng._sched.starvation_bound_ticks()
        assert eng._sched.max_wait_ticks_seen <= bound, \
            (eng._sched.max_wait_ticks_seen, bound)
        # aging actually changed picks (the flood exercised the
        # mechanism, not just the formula)
        assert registry().counter(
            "serving/aged_promotions").value > p0

    def test_pure_sjf_starves_where_aged_does_not(self):
        """The contrast that justifies the aging term: the SAME flood
        under pure SJF parks the long prompt past the aged bound (it
        only runs when the short supply dries up)."""
        eng = _flood("sjf")
        aged_bound = ChunkScheduler(
            "aged-sjf", 3, eng.pool.slot_capacity,
            eng.prefill_chunk, 1).starvation_bound_ticks()
        assert eng._sched.max_wait_ticks_seen > aged_bound, \
            (eng._sched.max_wait_ticks_seen, aged_bound)


# ---------------------------------------------------------------------------
# budget shaping in the engine
# ---------------------------------------------------------------------------
class TestBudgetShapingInEngine:
    def test_decode_pressure_cuts_budget_and_counts(self):
        """With half the slots decoding and nothing queued, a shaped
        engine selects fewer chunks than the compiled worst case
        (counted in serving/budget_cuts) — and still finishes
        everything. The compiled tick shape is untouched: the site
        traces once across shaped and unshaped ticks."""
        from paddle_tpu.profiler import recompile, registry

        net = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=4, page_size=8, pages_per_slot=4,
            prefill_chunk=8, prefill_chunks_per_tick=2,
            scheduler="sjf"))
        c0 = registry().counter("serving/budget_cuts").value
        short = _prompts((8, 8), seed=7)
        eng.submit(short[0], 16)
        eng.submit(short[1], 16)
        for _ in range(3):              # prefill both, start decoding
            eng.step()
        longs = _prompts((24, 24), seed=9)
        r2 = [eng.submit(p, 4) for p in longs]
        out = eng.run()
        assert registry().counter(
            "serving/budget_cuts").value > c0
        assert all(r in out for r in r2)
        assert recompile.trace_counts()[eng._tick_site] == 1

    def test_chunk_wait_histogram_records_per_admission(self):
        from paddle_tpu.profiler import registry

        net = _net()
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=3,
            prefill_chunk=8))
        h0 = registry().histogram("serving/chunk_wait_ms").count
        for p in _prompts((8, 16, 8)):
            eng.submit(p, 4)
        eng.run()
        # one admission->first-chunk sample per admission cycle
        assert registry().histogram(
            "serving/chunk_wait_ms").count == h0 + 3


# ---------------------------------------------------------------------------
# adaptive spec-k (engine level)
# ---------------------------------------------------------------------------
def _ind_draft(seed=7):
    paddle.seed(seed)
    net = GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=64,
                        initializer_range=0.2))
    net.eval()
    return net


class TestAdaptiveSpecK:
    def _spec_eng(self, net, draft, adaptive):
        # the draft's KV pages come from the target's pool (ISSUE 20),
        # best effort: 2 slots x 3 pages for each, plus the null page, or
        # the slot that finds the pool full never drafts, so that its
        # depth is never observed
        return ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=3, num_pages=13,
            prefill_chunk=8,
            spec=SpecConfig(draft_model=draft, k=4,
                            adaptive=adaptive)))

    def test_twin_draft_keeps_full_depth(self):
        """~100% acceptance: the EWMA never leaves 1.0 mid-residency,
        every offered depth is the full k (spec_k_effective gauge),
        and output stays bitwise dense generate()."""
        from paddle_tpu.profiler import registry

        net = _net()
        twin = _net()
        eng = self._spec_eng(net, twin, adaptive=True)
        prompts = _prompts((8, 16))
        rids = [eng.submit(p, 24 - len(p)) for p in prompts]
        k_effs = []
        while not eng.idle():
            eng.step()
            k_effs.append(registry().gauge(
                "serving/spec_k_effective").value)
            for s, rid in enumerate(eng._slot_rid):
                if rid is not None and not eng._requests[rid].done:
                    assert eng._spec_ctl.ewma(s) == 1.0
        out = {r: np.asarray(q.out, np.int32)
               for r, q in eng._requests.items() if q.done}
        for p, rid in zip(prompts, rids):
            np.testing.assert_array_equal(
                out[rid], _dense(net, p, 24 - len(p)))
        # full depth was offered on speculating ticks (budget/capacity
        # clamps can lower the tail ticks; the max must hit k)
        assert max(k_effs) == 4.0

    def test_independent_draft_decays_to_zero_and_stops_drafting(self):
        """~0% acceptance: every slot's depth decays to 0, after which
        the engine stops dispatching draft ticks entirely (plain-
        engine cost structure) — and the greedy stream is STILL
        bitwise the plain engine's / dense generate()'s (the
        acceptance invariant is depth-independent)."""
        from paddle_tpu.profiler import registry

        net = _net()
        eng = self._spec_eng(net, _ind_draft(), adaptive=True)
        prompts = _prompts((8, 8))
        rids = [eng.submit(p, 16) for p in prompts]
        # drive until both resident slots decayed to depth 0
        for _ in range(64):
            if eng.idle():
                break
            eng.step()
            live = [s for s, r in enumerate(eng._slot_rid)
                    if r is not None]
            if live and all(eng._spec_ctl.depth(s) == 0
                            for s in live):
                break
        live = [s for s, r in enumerate(eng._slot_rid)
                if r is not None]
        assert live and all(eng._spec_ctl.depth(s) == 0 for s in live)
        # decayed slots drop out of the draft tick: no more draft
        # dispatches, no more drafted tokens
        d0 = registry().counter("serving/spec_draft_ticks").value
        t0 = registry().counter("serving/spec_drafted_tokens").value
        for _ in range(6):
            if eng.idle():
                break
            eng.step()
        assert registry().counter(
            "serving/spec_draft_ticks").value == d0
        assert registry().counter(
            "serving/spec_drafted_tokens").value == t0
        out = eng.run()
        for p, rid in zip(prompts, rids):
            np.testing.assert_array_equal(out[rid],
                                          _dense(net, p, 16))

    def test_static_k_unchanged_by_default(self):
        """adaptive=False keeps the PR 9 behavior: no controller, full
        k offered regardless of acceptance."""
        net = _net()
        eng = self._spec_eng(net, _ind_draft(), adaptive=False)
        assert eng._spec_ctl is None


# ---------------------------------------------------------------------------
# sticky depth-0 re-probe (ISSUE 16 satellite, closing the PR 15 residue)
# ---------------------------------------------------------------------------
class TestSpecKReprobe:
    def _decayed(self, reprobe):
        from paddle_tpu.serving.sched import SpecKController

        c = SpecKController(num_slots=2, k=4, reprobe_every=reprobe)
        for _ in range(8):
            c.observe(0, 0, 4)           # ~0% acceptance
        assert c.depth(0) == 0
        return c

    def test_probe_fires_every_nth_zero_tick_and_latches(self):
        c = self._decayed(4)
        assert [c.tick_depth(0) for _ in range(4)] == [0, 0, 0, 1]
        # the probe LATCHES at depth 1 until its observation lands —
        # draft-feed catch-up can take ticks, and a fizzled probe must
        # not count as evidence
        assert c.probing(0)
        assert c.tick_depth(0) == 1
        c.observe(0, 0, 1)               # rejected: demotion confirmed
        assert not c.probing(0)
        assert c.depth(0) == 0
        # the cycle restarts with multiplicative backoff (ISSUE 20):
        # a rejected probe doubles the period, so the next probe costs
        # one drafted token per 2*reprobe_every zero-ticks
        assert c.probe_period(0) == 8
        assert [c.tick_depth(0) for _ in range(8)] == [0] * 7 + [1]

    def test_rejected_probes_back_off_and_accept_resets(self):
        c = self._decayed(2)
        periods = []
        for _ in range(6):
            while c.tick_depth(0) == 0:
                pass                     # advance to the next probe
            c.observe(0, 0, 1)           # rejected again
            periods.append(c.probe_period(0))
        # doubles per consecutive rejection, capped at 8x the base
        assert periods == [4, 8, 16, 16, 16, 16]
        while c.tick_depth(0) == 0:
            pass
        c.observe(0, 1, 1)               # accepted: full cadence back
        assert c.probe_period(0) == 2

    def test_reset_restores_base_probe_period(self):
        c = self._decayed(2)
        while c.tick_depth(0) == 0:
            pass
        c.observe(0, 0, 1)
        assert c.probe_period(0) == 4
        c.reset(0)
        assert c.probe_period(0) == 2

    def test_accepted_probe_reopens_the_depth(self):
        c = self._decayed(2)
        assert [c.tick_depth(0) for _ in range(2)] == [0, 1]
        c.observe(0, 1, 1)               # accepted: EWMA back to ~0.5
        assert c.depth(0) >= 1           # speculating again
        assert c.tick_depth(0) == c.depth(0)

    def test_reprobe_zero_disables(self):
        # the documented PR 15 behavior is reprobe_every=0: a decayed
        # slot never drafts again for its residency
        c = self._decayed(0)
        assert all(c.tick_depth(0) == 0 for _ in range(50))

    def test_depth_stays_pure(self):
        c = self._decayed(3)
        for _ in range(50):
            assert c.depth(0) == 0       # no probe side effects
        assert c.tick_depth(0) == 0      # counter untouched by depth()

    def test_reset_clears_probe_state(self):
        c = self._decayed(2)
        c.tick_depth(0)
        c.tick_depth(0)
        assert c.probing(0)
        c.reset(0)                       # new tenant: optimistic again
        assert not c.probing(0) and c.depth(0) == 4

    def test_slots_probe_independently(self):
        c = self._decayed(2)             # slot 0 decayed, slot 1 fresh
        assert c.tick_depth(1) == 4
        assert [c.tick_depth(0) for _ in range(2)] == [0, 1]
        assert c.tick_depth(1) == 4      # untouched by slot 0's probe

    def test_engine_reprobe_resumes_drafting_bitwise(self):
        """End-to-end: after a slot decays to 0 under an independent
        draft, a small ``reprobe_every`` makes the engine draft again
        (the probe), and the greedy stream STAYS bitwise the dense
        reference — the acceptance invariant is probe-independent."""
        from paddle_tpu.profiler import registry

        net = _net()
        # pages for both slots' drafts too, as in TestAdaptiveSpecK
        eng = ServingEngine(net, ServingConfig(
            num_slots=2, page_size=8, pages_per_slot=3, num_pages=13,
            prefill_chunk=8,
            spec=SpecConfig(draft_model=_ind_draft(), k=4,
                            adaptive=True, reprobe_every=2)))
        prompts = _prompts((8, 8))
        rids = [eng.submit(p, 16) for p in prompts]
        for _ in range(64):
            if eng.idle():
                break
            eng.step()
            live = [s for s, r in enumerate(eng._slot_rid)
                    if r is not None]
            if live and all(eng._spec_ctl.depth(s) == 0
                            for s in live):
                break
        live = [s for s, r in enumerate(eng._slot_rid)
                if r is not None]
        assert live and all(eng._spec_ctl.depth(s) == 0 for s in live)
        t0 = registry().counter("serving/spec_drafted_tokens").value
        for _ in range(6):
            if eng.idle():
                break
            eng.step()
        # unlike reprobe_every=0 (see the decay test above), the
        # probe drafts again within the window
        assert registry().counter(
            "serving/spec_drafted_tokens").value > t0
        out = eng.run()
        for p, rid in zip(prompts, rids):
            np.testing.assert_array_equal(out[rid],
                                          _dense(net, p, 16))


# ---------------------------------------------------------------------------
# load-shaped routing key (pure)
# ---------------------------------------------------------------------------
class TestTtfcKey:
    def _vote(self, backlog=0, p95=0.0, queued=0, free_slots=4,
              chunk=16):
        return {"prefill_backlog": backlog, "ttft_p95_ms": p95,
                "queued": queued, "free_slots": free_slots,
                "chunk": chunk, "free_pages": 100}

    def test_backlog_orders_in_chunk_train_units(self):
        votes = {0: self._vote(backlog=64), 1: self._vote(backlog=0)}
        k0 = ttfc_key(votes, 0, {}, {})
        k1 = ttfc_key(votes, 1, {}, {})
        assert k1 < k0 and k0[0] == 4.0    # ceil(64/16) chunk trains

    def test_round_local_assignments_accumulate(self):
        votes = {0: self._vote(), 1: self._vote()}
        # 32 tokens already assigned to rank 0 this round
        assert ttfc_key(votes, 1, {0: 32}, {}) < \
            ttfc_key(votes, 0, {0: 32}, {})

    def test_p95_breaks_backlog_ties(self):
        votes = {0: self._vote(p95=500.0), 1: self._vote(p95=10.0)}
        assert ttfc_key(votes, 1, {}, {}) < ttfc_key(votes, 0, {}, {})

    def test_slot_overflow_penalty(self):
        votes = {0: self._vote(free_slots=1), 1: self._vote(free_slots=4)}
        # two requests already assigned to each: rank 0 overflows
        assert ttfc_key(votes, 1, {}, {0: 2, 1: 2}) < \
            ttfc_key(votes, 0, {}, {0: 2, 1: 2})

    def test_page_pressure_outweighs_an_empty_queue(self):
        """A rank with zero backlog but a nearly-exhausted page pool
        must not win over a rank with a small backlog and a free pool:
        routing into page exhaustion buys preemption churn, not a
        short chunk wait (the old reducer's -free_pages term,
        re-expressed as a token-capacity deficit)."""
        votes = {0: self._vote(backlog=0, free_slots=4, chunk=16),
                 1: self._vote(backlog=32, free_slots=4, chunk=16)}
        votes[0]["free_pages"] = 1        # ~16 free tokens
        votes[0]["page_size"] = 16
        votes[1]["page_size"] = 16
        # 64 tokens already assigned to each this round: rank 0's
        # deficit (64 - 16) out-penalizes rank 1's backlog chunks
        assert ttfc_key(votes, 1, {0: 64, 1: 64}, {}) < \
            ttfc_key(votes, 0, {0: 64, 1: 64}, {})

    def test_legacy_vote_falls_back_to_queue_depth(self):
        old = {"queued": 3, "free_pages": 100, "free_slots": 4}
        votes = {0: dict(old, queued=0), 1: old}
        assert ttfc_key(votes, 0, {}, {}) < ttfc_key(votes, 1, {}, {})

    def test_missing_voter_prices_unroutable(self):
        votes = {0: self._vote()}
        assert ttfc_key(votes, 1, {}, {})[0] >= float(1 << 20)

    def test_route_requests_prefers_low_backlog_rank(self):
        """End-to-end through the reducer: symmetric topology, equal
        free pages, one rank with a deep prefill backlog — the shorts
        land on the shallow rank (the parked-shorts pathology the
        load-shaped vote retires)."""
        from paddle_tpu.serving import route_requests

        def vote(backlog, p95):
            return {"seen": 4, "routed": 0,
                    "pending": {str(g): 8 for g in range(4)},
                    "free_pages": 100, "free_slots": 4, "queued": 0,
                    "prefill_backlog": backlog, "ttft_p95_ms": p95,
                    "chunk": 16,
                    "topology": {"prefill": [], "decode": [0, 1],
                                 "threshold": 64}}

        out = route_requests({0: vote(256, 900.0), 1: vote(0, 5.0)})
        ranks = [d for _, d in out["assign"].values()]
        assert ranks.count(1) > ranks.count(0)
        # and deterministic across voter orderings
        assert out == route_requests(
            {1: vote(0, 5.0), 0: vote(256, 900.0)})
