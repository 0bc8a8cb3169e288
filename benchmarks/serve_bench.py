"""Serving throughput: continuous batching (paged KV) vs sequential
per-request ``generate()``, plus the prefix-caching TTFT comparison.

Default mode drives a Poisson arrival trace of mixed-prompt-length
requests against BOTH decode paths on the same weights:

  baseline   each request served alone, in arrival order, by the dense
             ``GPT.generate`` prefill+scan program (per-shape jit, warm)
  engine     ``paddle_tpu.serving.ServingEngine`` — requests admitted
             into cache slots as others finish, one fixed-shape decode
             tick advancing every resident request per dispatch

``--prefix-cache`` switches to the shared-system-prompt workload:
N concurrent requests sharing one system prompt with short unique
suffixes, served by a prefix-cache-ON engine vs a prefix-cache-OFF
engine (both with chunked prefill, both warm). Headline: mean-TTFT
ratio — the cached engine aliases the shared prompt's pages and
prefills only each request's suffix, so first tokens arrive without
re-running the system prompt per request. The profiler block carries
``serving/prefix_hit_tokens`` as the direct evidence.

The baseline is exactly what a naive deployment of this repo would run
today, warmed so the comparison is decode-vs-decode, not
compile-vs-decode.

Prints ONE JSON line (driver contract, same shape as bench.py).

The Poisson and --prefix-cache blocks carry the full registry
snapshot, the per-request latency-breakdown table + rolling TTFT/TPOT
p50/p90/p95/p99 (profiler event timelines), the compiled-program
inventory (compile wall-time + cost-analysis FLOPs/bytes per dispatch
site), and the measured event-log overhead on the decode hot loop.
``--sink-dir`` additionally streams everything to disk (metrics.jsonl
+ events.jsonl + metrics.prom — the ISSUE 8 persistent-sink artifact;
tools/check_sink_schema.py validates it in CI).
``--trace-window N`` (ISSUE 11) drives N extra warm ticks under a
parsed XLA device-trace window and embeds the MEASURED per-tick
device timeline — op-category timings, per-collective durations by
kind next to their modeled bytes, the compute∩comm overlap fraction,
and the goodput/MFU ledger — as ``extra.device_trace`` (plus
``trace_summary.json`` in the sink dir when ``--sink-dir`` is on).

``--sched-policy {fifo,sjf,aged-sjf}`` (ISSUE 15) selects the
engine's chunk-selection policy for the single-workload modes;
``--sched-matrix`` runs the long-prompt-mixed workload under all
three (p95 TTFT + tokens/s per policy — the parked-shorts
comparison), and ``--adaptive-k`` compares adaptive vs static
spec-k on a mixed-accept-rate workload (position-fenced twin draft;
outputs asserted bitwise between arms). BENCH_SERVE_r15.json holds
full runs of both.

    python benchmarks/serve_bench.py                 # Poisson, 8 slots
    python benchmarks/serve_bench.py --prefix-cache  # shared-prefix TTFT
    python benchmarks/serve_bench.py --sched-matrix  # fifo/sjf/aged-sjf
    python benchmarks/serve_bench.py --adaptive-k    # adaptive spec-k
    python benchmarks/serve_bench.py --elastic       # kill-one redispatch
    python benchmarks/serve_bench.py --tiny [...]    # CI smoke sizes
    python benchmarks/serve_bench.py --sink-dir DIR  # + persistent sink
    python benchmarks/serve_bench.py --trace-window 8  # + device trace
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_model(tiny: bool):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, GPTConfig

    paddle.seed(0)
    if tiny:
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                        num_heads=4, max_seq_len=128,
                        initializer_range=0.2)
    else:
        # still "tiny GPT" by training standards, but enough compute per
        # token that the comparison measures batching, not dispatch noise
        cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=6,
                        num_heads=8, max_seq_len=256,
                        initializer_range=0.2)
    net = GPT(cfg)
    net.eval()
    return net


def make_trace(n_requests, prompt_lens, max_new, arrival_rate_hz, seed=7):
    """Poisson arrivals: (arrival_s, prompt, max_new) sorted by time."""
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / arrival_rate_hz, size=n_requests)
    arrivals = np.cumsum(gaps)
    vocab_hi = 128
    trace = []
    for i in range(n_requests):
        t0 = int(prompt_lens[i % len(prompt_lens)])
        trace.append((float(arrivals[i]),
                      rng.randint(0, vocab_hi, (t0,)).astype(np.int32),
                      int(max_new)))
    return trace


def make_shared_prefix_requests(n, sys_len, sfx_len, max_new, seed=7):
    """n prompts = one shared system prompt + a unique suffix each."""
    rng = np.random.RandomState(seed)
    system = rng.randint(0, 128, (sys_len,)).astype(np.int32)
    return [(np.concatenate(
        [system, rng.randint(0, 128, (sfx_len,)).astype(np.int32)]),
        int(max_new)) for _ in range(n)]


def run_baseline(net, trace):
    """Sequential per-request dense generate over the arrival trace."""
    import paddle_tpu as paddle

    t_start = time.perf_counter()
    tokens = 0
    ttfts = []
    for arrival, prompt, max_new in trace:
        now = time.perf_counter() - t_start
        if now < arrival:
            time.sleep(arrival - now)
        req_t0 = time.perf_counter()
        ids, _ = net.generate(paddle.to_tensor(prompt[None]),
                              max_new_tokens=max_new)
        out = ids.numpy()          # materialize: the request is only
        tokens += out.shape[1]     # served once the host has the ids
        ttfts.append((time.perf_counter() - max(
            req_t0, t_start + arrival)) * 1000.0)
    wall = time.perf_counter() - t_start
    return tokens, wall, ttfts


def build_engine(net, num_slots, page_size, pages_per_slot,
                 prefill_chunk=0, prefix_cache=True, kv_dtype=None,
                 scheduler="fifo", prefill_chunks_per_tick=1):
    from paddle_tpu.serving import ServingConfig, ServingEngine

    return ServingEngine(net, ServingConfig(
        num_slots=num_slots, page_size=page_size,
        pages_per_slot=pages_per_slot, prefill_chunk=prefill_chunk,
        prefix_cache=prefix_cache, kv_dtype=kv_dtype, scheduler=scheduler,
        prefill_chunks_per_tick=prefill_chunks_per_tick))


def run_engine(eng, trace):
    """Drive the arrival trace through a (warm) engine instance."""
    eng.reset_results()
    t_start = time.perf_counter()
    pending = list(trace)
    batch_occupancy = []
    page_utils = []
    while pending or not eng.idle():
        now = time.perf_counter() - t_start
        while pending and pending[0][0] <= now:
            _, prompt, max_new = pending.pop(0)
            eng.submit(prompt, max_new)
        progressed = eng.step()
        batch_occupancy.append(
            sum(r is not None for r in eng._slot_rid))
        page_utils.append(eng.pool.allocator.utilization())
        if not progressed:
            if eng._inflight:
                eng.drain(0)
            elif pending:
                time.sleep(max(0.0, pending[0][0] - (
                    time.perf_counter() - t_start)))
    eng.drain(0)
    results = {rid: r for rid, r in eng._requests.items() if r.done}
    tokens = sum(len(r.out) for r in results.values())
    wall = time.perf_counter() - t_start
    ttfts = [(r.first_token_t - r.submit_t) * 1000.0
             for r in results.values() if r.first_token_t]
    return tokens, wall, ttfts, batch_occupancy, page_utils


def run_concurrent(eng, reqs):
    """Submit every request up front, run to completion."""
    eng.reset_results()
    t_start = time.perf_counter()
    for prompt, max_new in reqs:
        eng.submit(prompt, max_new)
    eng.run()
    wall = time.perf_counter() - t_start
    results = {rid: r for rid, r in eng._requests.items() if r.done}
    tokens = sum(len(r.out) for r in results.values())
    ttfts = [(r.first_token_t - r.submit_t) * 1000.0
             for r in results.values() if r.first_token_t]
    return tokens, wall, ttfts


def pct(xs, p):
    # the registry/event-timeline nearest-rank convention — the bench
    # block must report the same p95 as the sink for the same data
    from paddle_tpu.profiler.metrics import percentile

    return float(percentile(sorted(xs), p)) if xs else 0.0


def traced_window_block(eng, reqs, ticks):
    """Drive up to ``ticks`` ticks of the WARM engine under a parsed
    device-trace window (ISSUE 11) and return the summary: measured
    per-op-category timings, per-collective durations, the
    compute∩comm overlap fraction and the goodput/MFU ledger, per
    tick. Runs OFF the throughput clock (after the measured
    comparison) so the capture overhead never pollutes the headline;
    leftover requests finish outside the capture."""
    eng.reset_results()
    for prompt, max_new in reqs:
        eng.submit(prompt, max_new)
    with eng.trace_window() as cap:
        for _ in range(ticks):
            if eng.idle():
                break
            eng.step()
        eng.drain(0)          # sync before the trace stops
    while not eng.idle():     # finish residents off the trace
        if not eng.step():
            eng.drain(0)
    eng.reset_results()
    return cap.summary


def bench_poisson(args, tiny):
    import paddle_tpu as paddle
    import paddle_tpu.profiler as profiler

    n_req = 6 if tiny else args.requests
    max_new = 16 if tiny else args.max_new
    slots = 4 if tiny else args.slots
    prompt_lens = (8, 16) if tiny else (16, 32, 64)
    page_size = 8 if tiny else 16
    cap_tokens = max(prompt_lens) + max_new
    pages_per_slot = -(-cap_tokens // page_size)

    net = build_model(tiny)
    trace = make_trace(n_req, prompt_lens, max_new, args.rate)

    # ---- warm both paths (compile excluded from the measurement: the
    # engine instance is reused, so its compiled programs are traced
    # here, not on the clock) ----
    for t0 in prompt_lens:
        p = np.zeros((t0,), np.int32)
        net.generate(paddle.to_tensor(p[None]), max_new_tokens=max_new)
    eng = build_engine(net, slots, page_size, pages_per_slot,
                       scheduler=args.sched_policy)
    warm = make_trace(max(2, slots), prompt_lens, max_new, 1e9, seed=1)
    run_engine(eng, [(0.0, p, m) for _, p, m in warm])
    eng.pool.drop_prefix_cache()

    # ---- event-log overhead: the SAME warm engine + trace with event
    # emission off vs on. Its hot-loop cost is what the ISSUE 8
    # acceptance bounds (<2% tokens/s); the sink's background flush
    # thread never sits on the hot loop, so events are the whole of
    # the per-tick overhead surface. Single-run wall clocks on this
    # box swing far more than the effect being measured, so both arms
    # run ``reps`` times INTERLEAVED (drift hits both equally) and the
    # comparison is best-of-reps per arm.
    from paddle_tpu.profiler import events as _pevents

    reps = max(2, args.reps)
    off_tps = on_tps = 0.0
    for _ in range(reps):
        for enabled in (False, True):
            _pevents.set_enabled(enabled)
            eng.pool.drop_prefix_cache()
            toks, wall, *_ = run_engine(eng, trace)
            if enabled:
                on_tps = max(on_tps, toks / wall)
            else:
                off_tps = max(off_tps, toks / wall)
    _pevents.set_enabled(True)
    eng.pool.drop_prefix_cache()        # measured run starts cold

    # ---- live-aggregation overhead (ISSUE 16): the same warm engine
    # + trace with the LiveAggregator off vs ticking FAST (20 Hz —
    # far above the real ~0.5 Hz cadence, so the bound is
    # conservative). Publication is fire-and-forget inside the sink's
    # flush and the aggregator is a reader thread, so the serving
    # cost surface is thread/FS contention only. De-noising: MEDIAN
    # of per-rep PAIRED on/off ratios (the sched-matrix precedent —
    # pairing cancels drift, the median rejects a descheduled rep).
    live_overhead = live_reps = None
    if getattr(args, "live_status", None):
        from paddle_tpu.profiler.live import LiveAggregator

        live_reps = max(2, args.reps)
        ratios = []
        for _ in range(live_reps):
            eng.pool.drop_prefix_cache()
            toks, wall, *_ = run_engine(eng, trace)
            off = toks / wall
            agg = LiveAggregator(args.live_status, interval_s=0.05,
                                 staleness_s=1e9, emit_alerts=False)
            agg.start()
            eng.pool.drop_prefix_cache()
            toks, wall, *_ = run_engine(eng, trace)
            agg.stop(final_tick=False)
            ratios.append((toks / wall) / off if off else 1.0)
        ratios.sort()
        live_overhead = round(
            (1.0 - ratios[len(ratios) // 2]) * 100.0, 2)
        eng.pool.drop_prefix_cache()

    profiler.enable()
    bl_tokens, bl_wall, bl_ttft = run_baseline(net, trace)
    eng_tokens, eng_wall, eng_ttft, occ, putil = run_engine(eng, trace)
    lat_rows = profiler.latency_table()
    lat_stats = profiler.request_latency_stats()
    inventory = eng.record_program_stats()
    summ = profiler.disable()

    trace_block = None
    if args.trace_window:
        trace_block = traced_window_block(
            eng, [(p, m) for _, p, m in make_trace(
                max(2, slots), prompt_lens, max_new, 1e9, seed=3)],
            args.trace_window)

    bl_tps = bl_tokens / bl_wall
    eng_tps = eng_tokens / eng_wall
    speedup = eng_tps / bl_tps if bl_tps else 0.0
    overhead_pct = (off_tps - on_tps) / off_tps * 100.0 if off_tps \
        else 0.0
    snap = {k: v.get("value", v.get("count"))
            for k, v in summ["metrics"].items()
            if k.startswith("serving/")}
    out = {
        "metric": "serving_continuous_batching_speedup",
        "value": round(speedup, 4),
        "unit": "x tokens/s vs sequential generate()",
        "extra": {
            "mode": "tiny" if tiny else "full",
            "model": {"hidden": net.config.hidden_size,
                      "layers": net.config.num_layers,
                      "vocab": net.config.vocab_size},
            "requests": n_req, "slots": slots,
            "prompt_lens": list(prompt_lens), "max_new": max_new,
            "arrival_rate_hz": args.rate,
            "page_size": page_size, "pages_per_slot": pages_per_slot,
            "engine_tokens_per_sec": round(eng_tps, 2),
            "baseline_tokens_per_sec": round(bl_tps, 2),
            "engine_tokens": eng_tokens, "baseline_tokens": bl_tokens,
            "page_util_mean": round(float(np.mean(putil)), 4),
            "page_util_max": round(float(np.max(putil)), 4),
            "resident_mean": round(float(np.mean(occ)), 2),
            "ttft_ms": {"engine_p50": round(pct(eng_ttft, 50), 2),
                        "engine_p95": round(pct(eng_ttft, 95), 2),
                        "baseline_p50": round(pct(bl_ttft, 50), 2),
                        "baseline_p95": round(pct(bl_ttft, 95), 2)},
            # per-request latency breakdowns + rolling TTFT/TPOT
            # percentiles from the event timelines, the full registry
            # snapshot, and the compiled-program inventory (ISSUE 8)
            "request_latency": lat_stats,
            "latency_table": lat_rows,
            "registry": summ["metrics"],
            "xla_programs": inventory,
            # parsed device-trace window (ISSUE 11): per-tick
            # site/collective/MFU tables — measured, not apportioned
            "device_trace": trace_block,
            "events_overhead_pct": round(overhead_pct, 2),
            "events_off_tokens_per_sec": round(off_tps, 2),
            "events_on_tokens_per_sec": round(on_tps, 2),
            "events_overhead_reps": reps,
            "profiler": snap,
            "note": ("baseline pays one dense [1, S_max] cache + scan "
                     "program per request; the engine amortizes one "
                     "fixed-shape batch tick across every resident "
                     "request — measured warm on the box's default "
                     "jax backend, compile excluded for both. "
                     "events_overhead_pct "
                     "compares best-of-reps events-off vs events-on "
                     "runs of the same warm engine+trace, interleaved "
                     "(lifecycle-edge emission is the whole hot-loop "
                     "cost; the sink flushes on a background thread); "
                     "residual small/negative values are timer noise"),
        },
    }
    if trace_block is None:
        del out["extra"]["device_trace"]
    if live_overhead is not None:
        out["extra"]["live_overhead_pct"] = live_overhead
        out["extra"]["live_overhead_reps"] = live_reps
    return out


def bench_shared_prefix(args, tiny):
    import paddle_tpu.profiler as profiler

    slots = 4 if tiny else args.slots
    n_req = slots                       # all concurrent
    sys_len = 32 if tiny else 64
    sfx_len = 8
    max_new = 8 if tiny else 32
    page_size = 8 if tiny else 16
    cap_tokens = sys_len + sfx_len + max_new
    pages_per_slot = -(-cap_tokens // page_size)
    chunk = 2 * page_size

    net = build_model(tiny)
    reqs = make_shared_prefix_requests(n_req, sys_len, sfx_len, max_new)

    def fresh(prefix_cache):
        eng = build_engine(net, slots, page_size, pages_per_slot,
                           prefill_chunk=chunk,
                           prefix_cache=prefix_cache,
                           scheduler=args.sched_policy)
        # warm every compiled program (tick, prefill chunk, COW copy)
        # off the clock, then flush results + cached pages so the
        # measured run starts cold
        run_concurrent(eng, reqs)
        eng.pool.pools = eng._copy(eng.pool.pools, np.int32(0),
                                   np.int32(0))
        eng.pool.drop_prefix_cache()
        eng.reset_results()
        return eng

    eng_off = fresh(prefix_cache=False)
    eng_on = fresh(prefix_cache=True)

    # one profiler window PER engine (enable resets the registry), so
    # the evidence block for the cache-on run is not diluted by the
    # cache-off engine's counters
    profiler.enable()
    off_tokens, off_wall, off_ttft = run_concurrent(eng_off, reqs)
    summ_off = profiler.disable()
    profiler.enable()
    on_tokens, on_wall, on_ttft = run_concurrent(eng_on, reqs)
    lat_rows = profiler.latency_table()     # cache-on window only
    lat_stats = profiler.request_latency_stats()
    inventory = eng_on.record_program_stats()
    summ = profiler.disable()

    trace_block = None
    if args.trace_window:
        trace_block = traced_window_block(eng_on, reqs,
                                          args.trace_window)

    mean_off = float(np.mean(off_ttft))
    mean_on = float(np.mean(on_ttft))
    speedup = mean_off / mean_on if mean_on else 0.0

    def _snap(s):
        return {k: v.get("value", v.get("count"))
                for k, v in s["metrics"].items()
                if k.startswith(("serving/", "cache_share/"))}

    snap = _snap(summ)
    snap_off = _snap(summ_off)
    out = {
        "metric": "serving_prefix_cache_ttft_speedup",
        "value": round(speedup, 4),
        "unit": "x lower mean TTFT vs prefix-cache-off engine",
        "extra": {
            "mode": "tiny" if tiny else "full",
            "model": {"hidden": net.config.hidden_size,
                      "layers": net.config.num_layers,
                      "vocab": net.config.vocab_size},
            "requests": n_req, "slots": slots,
            "system_prompt_tokens": sys_len,
            "suffix_tokens": sfx_len, "max_new": max_new,
            "page_size": page_size, "pages_per_slot": pages_per_slot,
            "prefill_chunk": chunk,
            "ttft_ms": {
                "cache_mean": round(mean_on, 2),
                "cache_p50": round(pct(on_ttft, 50), 2),
                "cache_p95": round(pct(on_ttft, 95), 2),
                "nocache_mean": round(mean_off, 2),
                "nocache_p50": round(pct(off_ttft, 50), 2),
                "nocache_p95": round(pct(off_ttft, 95), 2)},
            "cache_tokens_per_sec": round(on_tokens / on_wall, 2),
            "nocache_tokens_per_sec": round(off_tokens / off_wall, 2),
            "cache_tokens": on_tokens, "nocache_tokens": off_tokens,
            "request_latency": lat_stats,   # cache-on window only
            "latency_table": lat_rows,
            "registry": summ["metrics"],
            "xla_programs": inventory,
            "profiler": snap,             # cache-on engine only
            "profiler_nocache": snap_off,
            "note": ("N concurrent requests share one system prompt; "
                     "the cache-on engine prefills it once and every "
                     "later admission aliases those pages (refcounted) "
                     "and prefills only its unique suffix — chunked "
                     "prefill in both engines, both warm, greedy "
                     "decode (outputs bitwise-equal across engines)"),
        },
    }
    if trace_block is not None:
        out["extra"]["device_trace"] = trace_block
    return out


def _pool_bytes(eng):
    """Device bytes of an engine's page pool, scale arrays included —
    the honest denominator of the residency claim."""
    return sum(a.nbytes for a in eng.pool.pools.arrays().values())


def _continuation_nll(net, prompt, cont):
    """Per-token NLL of ``cont`` after ``prompt`` under the (f32,
    dense) reference model — the quality proxy's perplexity leg: how
    plausible each engine's emitted continuation is under the model
    that emitted it (KV quantization perturbs the sampling path, not
    the scoring model)."""
    import paddle_tpu as paddle

    seq = np.concatenate([prompt, np.asarray(cont, np.int32)])[None]
    logits = np.asarray(
        net(paddle.to_tensor(seq.astype(np.int32))).numpy(),
        np.float64)[0]
    lp = logits - np.log(np.exp(
        logits - logits.max(-1, keepdims=True)).sum(-1, keepdims=True)) \
        - logits.max(-1, keepdims=True)
    pos = np.arange(len(prompt) - 1, seq.shape[1] - 1)
    return -lp[pos, np.asarray(cont, np.int64)]


def bench_kv_quant(args, tiny):
    """int8 (or bf16) KV pages vs the f32 pool (ISSUE 12): a residency
    cell at MATCHED pool bytes (the int8 pool holds 2x the slots in
    about half the bytes — per-page scale overhead included) and a
    quality-proxy cell (greedy token-match rate vs the f32 engine on a
    fixed-seed workload, plus the dense-model perplexity of each
    engine's emitted continuations, reported honestly).

    Regime note: this mode uses STANDARD-init (0.02) untrained models.
    With the serving benches' usual 0.2-scale init, untrained
    attention logits saturate and greedy argmax sits on knife-edge
    ties — a sub-1% cache perturbation flips ~10% of tokens/step
    there (measured), which characterizes the regime's chaos, not the
    quantizer. The same reasoning as the --spec-decode draft-friendly
    regime; trained models land at or above the 0.02-init margin.
    """
    import paddle_tpu as paddle
    import paddle_tpu.profiler as profiler
    from paddle_tpu.models import GPT, GPTConfig

    kv = args.kv_dtype
    paddle.seed(0)
    if tiny:
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                        num_heads=4, max_seq_len=128)
        slots, n_req, max_new, plens, ps = 2, 6, 16, (8, 16), 8
    else:
        cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=4,
                        num_heads=4, max_seq_len=256)
        slots, n_req, max_new = args.slots // 2 or 4, args.requests, \
            args.max_new
        plens, ps = (16, 32, 64), 16
    net = GPT(cfg)
    net.eval()
    pages_per_slot = -(-(max(plens) + max_new) // ps)
    trace = make_trace(n_req, plens, max_new, args.rate)

    # ---- quality proxy: same fixed-seed workload through both pools -
    def outputs(eng):
        eng.reset_results()
        run_engine(eng, trace)
        res = {rid: r for rid, r in eng._requests.items() if r.done}
        out = [(res[rid].prompt[:res[rid].orig_prompt_len],
                np.asarray(res[rid].out, np.int32))
               for rid in sorted(res)]
        eng.reset_results()
        return out

    eng_f = build_engine(net, slots, ps, pages_per_slot)
    eng_q = build_engine(net, slots, ps, pages_per_slot, kv_dtype=kv)
    warm = make_trace(max(2, slots), plens, max_new, 1e9, seed=1)
    for eng in (eng_f, eng_q):
        run_engine(eng, [(0.0, p, m) for _, p, m in warm])
        eng.pool.drop_prefix_cache()
        eng.reset_results()

    profiler.enable()
    outs_f = outputs(eng_f)
    outs_q = outputs(eng_q)
    tot = mat = 0
    nll_f, nll_q = [], []
    for (pf, cf), (pq, cq) in zip(outs_f, outs_q):
        assert np.array_equal(pf, pq)
        for x, y in zip(cf, cq):
            tot += 1
            mat += int(x == y)
        nll_f.append(_continuation_nll(net, pf, cf))
        nll_q.append(_continuation_nll(net, pq, cq))
    ppl_f = float(np.exp(np.mean(np.concatenate(nll_f))))
    ppl_q = float(np.exp(np.mean(np.concatenate(nll_q))))
    quality = {
        "kv_dtype": kv, "requests": len(outs_f),
        "total_tokens": tot, "matched_tokens": mat,
        "token_match_rate": round(mat / max(tot, 1), 4),
        "ppl_f32": round(ppl_f, 4), "ppl_kv": round(ppl_q, 4),
        "ppl_delta": round(ppl_q - ppl_f, 4),
        "note": ("token_match_rate is positional equality of the two "
                 "greedy streams (one flip cascades — it lower-bounds "
                 "per-step agreement); ppl_* is the dense f32 model's "
                 "perplexity of each engine's own emitted "
                 "continuations on the same prompts"),
    }

    # ---- residency cell: matched pool bytes, 2x slots under int8 ----
    # f32 pool with `slots` fully-resident slots sets the byte budget;
    # the quantized pool fits 2x the slots (scales included) in less.
    res_f = build_engine(net, slots, ps, pages_per_slot)
    res_q = build_engine(net, 2 * slots, ps, pages_per_slot,
                         kv_dtype=kv)
    bytes_f, bytes_q = _pool_bytes(res_f), _pool_bytes(res_q)
    res_trace = make_trace(2 * n_req, plens, max_new, args.rate,
                           seed=13)
    for eng in (res_f, res_q):
        run_engine(eng, [(0.0, p, m) for _, p, m in warm])
        eng.pool.drop_prefix_cache()
        eng.reset_results()
    tok_f, wall_f, _, occ_f, _ = run_engine(res_f, res_trace)
    tok_q, wall_q, _, occ_q, _ = run_engine(res_q, res_trace)
    residency = {
        "f32_slots": slots, "kv_slots": 2 * slots,
        "f32_pool_bytes": bytes_f, "kv_pool_bytes": bytes_q,
        "pool_bytes_ratio": round(bytes_q / bytes_f, 4),
        "slots_ratio": 2.0,
        "f32_tokens_per_sec": round(tok_f / wall_f, 2),
        "kv_tokens_per_sec": round(tok_q / wall_q, 2),
        "f32_resident_mean": round(float(np.mean(occ_f)), 2),
        "kv_resident_mean": round(float(np.mean(occ_q)), 2),
    }

    lat_stats = profiler.request_latency_stats()
    lat_rows = profiler.latency_table()
    inventory = eng_q.record_program_stats()
    summ = profiler.disable()
    snap = {k: v.get("value", v.get("count"))
            for k, v in summ["metrics"].items()
            if k.startswith("serving/")}
    return {
        "metric": "serving_kv_quant_residency",
        # 2x slots, discounted if the quantized pool overshot the f32
        # byte budget (it never does: int8+scales is ~half the bytes
        # at double the slots)
        "value": round(2.0 * min(1.0, bytes_f / bytes_q), 4),
        "unit": f"x resident slots at matched pool bytes "
                f"({kv} vs f32 KV pages)",
        "extra": {
            "mode": "tiny" if tiny else "full",
            "model": {"hidden": cfg.hidden_size,
                      "layers": cfg.num_layers,
                      "vocab": cfg.vocab_size,
                      "initializer_range": cfg.initializer_range},
            "kv_dtype": kv, "page_size": ps,
            "pages_per_slot": pages_per_slot,
            "requests": n_req, "max_new": max_new,
            "prompt_lens": list(plens),
            "residency": residency,
            "kv_quality_proxy": quality,
            "request_latency": lat_stats,
            "latency_table": lat_rows,
            "registry": summ["metrics"],
            "xla_programs": inventory,
            "events_overhead_pct": None,
            "profiler": snap,
            "note": ("residency cell: the quantized pool carries 2x "
                     "the resident slots in pool_bytes_ratio of the "
                     "f32 bytes (int8 values + f32 per-page per-head "
                     "scales; the byte headroom is ~4x, the cell "
                     "claims the ISSUE's 2x with room to spare) on a "
                     "2x-concurrency Poisson workload. quality cell: "
                     "standard-init (0.02) untrained model — see the "
                     "mode docstring for why 0.2-init untrained "
                     "attention is a chaotic-regime measurement, not "
                     "a quantizer one. Quantize-on-write pays a "
                     "page-granular read-modify-write per token per "
                     "layer (rescale-on-growth), so CPU tokens/s "
                     "under int8 reads below f32 — the win this "
                     "change buys is HBM residency, which CPU wall "
                     "clock does not price"),
        },
    }


def build_early_exit_draft(net, layers):
    """A draft model that is the target's first ``layers`` blocks plus
    its embeddings/final-norm/head — the layer-skip self-drafting
    construction (Draft&Verify-style early exit). With GPT-2-scale
    init (0.02) the residual stream changes slowly per block, so the
    truncated model's argmax agrees with the full model's often enough
    to be a genuine draft-friendly regime WITHOUT any training; an
    independent random draft would accept ~0 and only measure
    overhead. Acceptance only affects speed, never output — the spec
    engine's greedy stream is bitwise the plain engine's either way
    (asserted below)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, GPTConfig

    c = net.config
    paddle.seed(1)
    d = GPT(GPTConfig(vocab_size=c.vocab_size, hidden_size=c.hidden_size,
                      num_layers=layers, num_heads=c.num_heads,
                      max_seq_len=c.max_seq_len,
                      initializer_range=c.initializer_range))
    d.eval()

    def copy_params(dst, src):
        for (_, dp), (_, sp) in zip(dst.named_parameters(),
                                    src.named_parameters()):
            dp.set_value(sp)

    copy_params(d.embeddings, net.embeddings)
    for i in range(layers):
        copy_params(d.blocks[i], net.blocks[i])
    copy_params(d.ln_f, net.ln_f)
    return d


def bench_spec(args, tiny):
    """Speculative vs plain engine, greedy, same weights and arrival
    trace per cell; outputs are asserted BITWISE equal between the two
    engines, so the measured delta is pure dispatch/overlap structure.
    The draft is an early-exit copy of the target (``--draft-layers``
    blocks, ``--draft-k`` tokens per verify).

    Two cells, because where speculation wins is a property of the
    REGIME, not the trick: the headline ``low_batch`` cell is
    decode-heavy at small residency — each tick underutilizes the
    backend, so verifying k+1 positions per dispatch is nearly free
    (this is the latency-bound regime real TPU decode lives in). The
    full mode adds a ``compute_bound`` cell (bigger model, full
    residency, Poisson arrivals) where CPU wall-clock is dominated by
    FLOPs — speculation never reduces target FLOPs (it removes
    sequential dispatches; rejected drafts + the draft itself ADD
    compute), so the margin there comes only from BLAS batching
    efficiency and shrinks toward (or below) 1x as the draft deepens —
    the measured draft-depth sensitivity is stated in the note. Best-of
    ``--reps`` per arm per cell.
    """
    import paddle_tpu as paddle
    import paddle_tpu.profiler as profiler
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.profiler import registry
    from paddle_tpu.serving import ServingConfig, ServingEngine, SpecConfig

    reps = max(1, args.reps)
    k = args.draft_k

    def make_net(hidden, layers, vocab, msl, heads):
        # draft-friendly greedy regime: DEFAULT init (0.02) so the
        # early-exit draft actually agrees with the target —
        # serve_bench's usual 0.2 init makes every layer matter and
        # the accept rate collapses; throughput, not output variety,
        # is what this mode measures (parity is asserted
        # engine-vs-engine regardless)
        paddle.seed(0)
        net = GPT(GPTConfig(vocab_size=vocab, hidden_size=hidden,
                            num_layers=layers, num_heads=heads,
                            max_seq_len=msl))
        net.eval()
        return net

    def measure(net, draft_layers, cell_k, slots, n_req, prompt_lens,
                max_new, rate, page_size):
        draft = build_early_exit_draft(net, draft_layers)
        pages_per_slot = -(-(max(prompt_lens) + max_new) // page_size)
        trace = make_trace(n_req, prompt_lens, max_new, rate)
        plain = build_engine(net, slots, page_size, pages_per_slot)
        spec = ServingEngine(net, ServingConfig(
            num_slots=slots, page_size=page_size,
            pages_per_slot=pages_per_slot,
            spec=SpecConfig(draft_model=draft, k=cell_k)))
        warm = make_trace(max(2, slots), prompt_lens, max_new, 1e9,
                          seed=1)
        for eng in (plain, spec):
            run_engine(eng, [(0.0, p, m) for _, p, m in warm])
            eng.pool.drop_prefix_cache()
            eng.reset_results()
        a0 = registry().counter("serving/spec_accepted_tokens").value
        d0 = registry().counter("serving/spec_drafted_tokens").value
        best = {"plain": 0.0, "spec": 0.0}
        outs = {}
        ticks = {}
        for _ in range(reps):
            for name, eng in (("plain", plain), ("spec", spec)):
                eng.pool.drop_prefix_cache()
                t0 = registry().counter("serving/ticks").value
                g0 = registry().counter(
                    "serving/tokens_generated").value
                ar0 = registry().counter(
                    "serving/spec_accepted_tokens").value
                toks, wall, *_ = run_engine(eng, trace)
                res = {r.prompt.tobytes(): list(r.out)
                       for r in eng._requests.values() if r.done}
                eng.reset_results()
                if toks / wall > best[name]:
                    best[name] = toks / wall
                    outs[name] = res
                    ticks[name] = (
                        registry().counter("serving/ticks").value - t0,
                        registry().counter(
                            "serving/tokens_generated").value - g0,
                        registry().counter(
                            "serving/spec_accepted_tokens").value - ar0)
        # the acceptance invariant, asserted on the bench path too
        assert outs["plain"] == outs["spec"], \
            "spec output diverged from plain greedy engine"
        accepted = registry().counter(
            "serving/spec_accepted_tokens").value - a0
        drafted = registry().counter(
            "serving/spec_drafted_tokens").value - d0
        return spec, {
            "model": {"hidden": net.config.hidden_size,
                      "layers": net.config.num_layers,
                      "vocab": net.config.vocab_size},
            "draft": {"layers": draft_layers, "k": cell_k},
            "slots": slots, "requests": n_req,
            "prompt_lens": list(prompt_lens), "max_new": max_new,
            "arrival_rate_hz": rate, "page_size": page_size,
            "speedup": round(best["spec"] / max(best["plain"], 1e-9), 4),
            "spec_tokens_per_sec": round(best["spec"], 2),
            "plain_tokens_per_sec": round(best["plain"], 2),
            "accept_rate": round(accepted / drafted, 4) if drafted
            else 0.0,
            "spec_verify_ticks": ticks["spec"][0],
            "plain_decode_ticks": ticks["plain"][0],
            # per best spec rep: ALL emissions (corrections, plain
            # rows, finisher firsts included) vs accepted DRAFTS only
            "tokens_per_verify_tick": round(
                ticks["spec"][1] / max(ticks["spec"][0], 1), 3),
            "accepted_tokens_per_verify_tick": round(
                ticks["spec"][2] / max(ticks["spec"][0], 1), 3),
        }

    profiler.enable()
    cells = {}
    dl = max(1, min(args.draft_layers, 3))
    if tiny:
        net = make_net(64, 4, 128, 128, 4)
        spec_eng, cells["low_batch"] = measure(
            net, dl, k, 4, 6, (8, 16), 32, 1e9, 8)
    else:
        net = make_net(64, 4, 128, 128, 4)
        spec_eng, cells["low_batch"] = measure(
            net, dl, k, 4, 8, (8, 16), 48, 1e9, 8)
        big = make_net(256, 6, 512, 256, 8)
        _, cells["compute_bound"] = measure(
            big, max(1, min(args.draft_layers, 5)), k, args.slots,
            args.requests, (16, 32, 64), args.max_new, args.rate, 16)
    lat_stats = profiler.request_latency_stats()
    lat_rows = profiler.latency_table()
    inventory = spec_eng.record_program_stats()
    summ = profiler.disable()
    snap = {kk: v.get("value", v.get("count"))
            for kk, v in summ["metrics"].items()
            if kk.startswith("serving/")}
    return {
        "metric": "serving_spec_decode_speedup",
        "value": cells["low_batch"]["speedup"],
        "unit": "x tokens/s, speculative vs plain engine "
                "(decode-heavy low-batch burst, greedy)",
        "extra": {
            "mode": "tiny" if tiny else "full",
            "cells": cells,
            "reps": reps,
            "draft_kind": "early-exit (first blocks of the target + "
                          "shared embeddings/head)",
            "request_latency": lat_stats,
            "latency_table": lat_rows,
            "registry": summ["metrics"],
            "xla_programs": inventory,
            "profiler": snap,
            "note": ("speculative greedy output asserted BITWISE "
                     "equal to the plain engine's in every cell (the "
                     "acceptance invariant). The draft is an "
                     "untrained early-exit copy of the target — with "
                     "0.02-scale init the truncated residual stream "
                     "agrees with the full model often (a genuinely "
                     "draft-friendly regime); trained draft/target "
                     "pairs land elsewhere on the accept-rate curve. "
                     "low_batch is the headline: small residency, "
                     "decode-heavy — each tick underutilizes the "
                     "backend, so one verify of k+1 positions beats "
                     "k+1 sequential ticks. compute_bound is the "
                     "honest stress cell: CPU wall-clock there equals "
                     "FLOPs, which speculation never reduces "
                     "(rejected drafts + the draft model ADD some) "
                     "and spec mode gives up the deferred-sync window "
                     "(acceptance must materialize before the next "
                     "tick is schedulable) — its margin is mostly "
                     "BLAS batching efficiency (one [rows, h] matmul "
                     "beats k+1 thin ones) and is draft-depth "
                     "sensitive: 1-layer drafts measured ~1.5x across "
                     "runs of both cells on this box, while a 2-layer "
                     "draft dropped compute_bound to 0.72x (draft "
                     "FLOPs are pure overhead there). Real TPU decode "
                     "is memory-latency-bound like low_batch, not "
                     "FLOPs-bound; CPU timing therefore understates "
                     "the TPU win"),
        },
    }


def bench_spec_sampling(args, tiny):
    """Sampled speculative decoding (ISSUE 20): three arms on the
    decode-heavy low-batch cell, identical weights/trace/keys —
    ``plain`` (sampled, no speculation), ``spec_sync`` (rejection
    sampling, synchronous absorb) and ``spec_overlap`` (the chained
    draft tick hides the per-tick sync). The sync and overlap arms are
    asserted token-for-token EQUAL (overlap is pure latency structure,
    invisible in the stream). The plain arm is the throughput
    baseline only: rejection sampling preserves the per-position
    DISTRIBUTION, not the per-key stream, once draft and target
    filtered supports overlap — stream-vs-plain equality at the accept
    extremes is pinned in tests/test_spec_sampling.py, not here.
    Best-of ``--reps`` per arm (noise-floor precedent)."""
    import paddle_tpu.profiler as profiler
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.profiler import registry
    from paddle_tpu.serving import ServingConfig, ServingEngine, SpecConfig

    reps = max(1, args.reps)
    k = args.draft_k
    dl = max(1, min(args.draft_layers, 3))
    temperature, top_k, top_p = 0.9, 20, 0.95

    def make_net(layers):
        # default init (0.02): the early-exit draft's filtered
        # distribution overlaps the target's, so the accept rate is a
        # property of the construction, not luck. DEEP and narrow:
        # sampled acceptance (~0.45 for a 1-block draft — the
        # rejection rule is strictly harsher than greedy argmax
        # agreement) needs the per-tick dispatch to be expensive
        # relative to the draft scan before speculation pays; depth
        # is sequential latency, which is exactly what the verify
        # tick amortizes
        import paddle_tpu as paddle

        paddle.seed(0)
        net = GPT(GPTConfig(vocab_size=128, hidden_size=64,
                            num_layers=layers, num_heads=4,
                            max_seq_len=128))
        net.eval()
        return net

    net = make_net(4 if tiny else 24)
    draft = build_early_exit_draft(net, dl)
    slots, page_size = 4, 8
    # n_req == slots, decode-heavy: the overlap arm's chained tick
    # replaces the catch-up draft tick 1:1 only in speculation steady
    # state — queue churn forces extra catch-up dispatches, which on a
    # synchronous-dispatch box is pure added cost
    n_req, max_new = (4, 24) if tiny else (4, 96)
    prompt_lens = (8, 16)
    pages_per_slot = -(-(max(prompt_lens) + max_new) // page_size)
    trace = make_trace(n_req, prompt_lens, max_new, 1e9)
    warm = make_trace(max(2, slots), prompt_lens, max_new, 1e9, seed=1)

    def build(spec):
        # pool sized for target + draft residency: the sync==overlap
        # stream assert below needs both arms to speculate on the
        # SAME schedule — under pool pressure the arms clamp/reclaim
        # draft pages at different ticks (each still samples the
        # exact per-position law, but the sample paths part at the
        # first differing proposal), which is the tight-pool regime
        # tests/test_spec_sampling.py covers, not this cell's. 3x
        # (not 2x) because prefix-cache entries keep prompt pages
        # allocated past slot release, eating into the headroom
        return ServingEngine(net, ServingConfig(
            num_slots=slots, page_size=page_size,
            pages_per_slot=pages_per_slot,
            num_pages=3 * slots * pages_per_slot + 1,
            decode="sampling", temperature=temperature,
            top_k=top_k, top_p=top_p, spec=spec))

    arms = {
        "plain": build(None),
        "spec_sync": build(SpecConfig(draft_model=draft, k=k)),
        "spec_overlap": build(SpecConfig(draft_model=draft, k=k,
                                         overlap=True)),
    }
    profiler.enable()
    for eng in arms.values():
        run_engine(eng, [(0.0, p, m) for _, p, m in warm])
        eng.pool.drop_prefix_cache()
        eng.reset_results()
    a0 = registry().counter("serving/spec_accepted_tokens").value
    d0 = registry().counter("serving/spec_drafted_tokens").value
    best = {name: 0.0 for name in arms}
    ticks = {}
    for _ in range(reps):
        rep_outs = {}
        for name, eng in arms.items():
            eng.pool.drop_prefix_cache()
            t0 = registry().counter("serving/ticks").value
            g0 = registry().counter("serving/tokens_generated").value
            toks, wall, *_ = run_engine(eng, trace)
            rep_outs[name] = {r.prompt.tobytes(): list(r.out)
                              for r in eng._requests.values() if r.done}
            eng.reset_results()
            if toks / wall > best[name]:
                best[name] = toks / wall
                ticks[name] = (
                    registry().counter("serving/ticks").value - t0,
                    registry().counter(
                        "serving/tokens_generated").value - g0)
        # the overlap invariant: chaining the next draft tick on the
        # verify tick's device outputs must not move a single token.
        # compare WITHIN the rep: request ids advance across reps, so
        # the engine-default per-request sampling keys (fold_in of the
        # rid) make rep N and rep N+1 different — equally valid —
        # streams
        assert rep_outs["spec_sync"] == rep_outs["spec_overlap"], \
            "overlap arm diverged from synchronous-absorb arm"
    accepted = registry().counter(
        "serving/spec_accepted_tokens").value - a0
    drafted = registry().counter(
        "serving/spec_drafted_tokens").value - d0
    share_peak = registry().gauge(
        "serving/draft_pool_share_peak").value
    inventory = arms["spec_overlap"].record_program_stats()
    lat_stats = profiler.request_latency_stats()
    summ = profiler.disable()
    cell = {
        "model": {"hidden": net.config.hidden_size,
                  "layers": net.config.num_layers,
                  "vocab": net.config.vocab_size},
        "draft": {"layers": dl, "k": k},
        "sampling": {"temperature": temperature, "top_k": top_k,
                     "top_p": top_p},
        "slots": slots, "requests": n_req,
        "prompt_lens": list(prompt_lens), "max_new": max_new,
        "page_size": page_size,
        "plain_tokens_per_sec": round(best["plain"], 2),
        "spec_sync_tokens_per_sec": round(best["spec_sync"], 2),
        "spec_overlap_tokens_per_sec": round(best["spec_overlap"], 2),
        "speedup_sync": round(
            best["spec_sync"] / max(best["plain"], 1e-9), 4),
        "speedup_overlap": round(
            best["spec_overlap"] / max(best["plain"], 1e-9), 4),
        "overlap_vs_sync": round(
            best["spec_overlap"] / max(best["spec_sync"], 1e-9), 4),
        "accept_rate": round(accepted / drafted, 4) if drafted else 0.0,
        "drafted_tokens": int(drafted),
        "accepted_tokens": int(accepted),
        "tokens_per_verify_tick": round(
            ticks["spec_overlap"][1]
            / max(ticks["spec_overlap"][0], 1), 3),
        "draft_pool_share_peak": round(share_peak or 0.0, 4),
    }
    return {
        "metric": "serving_spec_sampling_speedup",
        "value": cell["speedup_overlap"],
        "unit": "x tokens/s, sampled speculative (overlap arm) vs "
                "sampled plain engine (decode-heavy low-batch burst)",
        "extra": {
            "mode": "tiny" if tiny else "full",
            "cells": {"spec_sampling": cell},
            "reps": reps,
            "draft_kind": "early-exit (first blocks of the target + "
                          "shared embeddings/head)",
            "request_latency": lat_stats,
            "registry": summ["metrics"],
            "xla_programs": inventory,
            "note": ("spec_sync and spec_overlap outputs asserted "
                     "token-for-token equal — the chained draft tick "
                     "is pure latency structure. The plain arm is a "
                     "throughput baseline, not a stream pin: "
                     "rejection sampling with both distributions "
                     "filtered by the same temperature/top-k/top-p "
                     "preserves the per-position law exactly "
                     "(fixed-key equality at both accept extremes is "
                     "pinned in tests/test_spec_sampling.py), but a "
                     "mid-spectrum draft re-randomizes the stream at "
                     "the first rejection. draft_pool_share_peak is "
                     "the draft cache's high-water share of ALL "
                     "allocated pages — draft KV now lives on the "
                     "shared PagePool allocator, priced by the same "
                     "residency ledger as target bytes"),
        },
    }


def bench_sched_matrix(args, tiny):
    """Chunk-selection policies on the long-prompt-mixed workload
    (ISSUE 15): the single-host version of the pathology
    BENCH_SERVE_r13 measured on the symmetric mesh — mostly-short
    traffic plus a couple of very long prompts, where fifo
    (oldest-admission-first) parks every short admitted behind a long
    behind the long's ENTIRE chunk train. One cell per policy
    (fifo / sjf / aged-sjf), same warm engine shape, same arrival
    trace; headline = fifo p95 TTFT / aged-sjf p95 TTFT (>1 means the
    policy retired the parked-shorts pathology), with the tokens/s
    ratio reported next to it (the ISSUE bounds the cost at <= 5%).
    Per-cell evidence: serving/chunk_wait_ms p95 (admission -> first
    chunk open), budget_cuts, aged_promotions. Reps run INTERLEAVED
    across policies and the headline is the median of per-rep PAIRED
    ratios — this box's per-rep tick speed swings more than the
    structural effect, and unpaired best-of-reps compares one cell's
    luckiest rep against another's (the events-overhead de-noising
    precedent, taken one step further)."""
    import paddle_tpu.profiler as profiler

    # ONE long in n requests, with n sized so the nearest-rank p95
    # (index int(.95n)) excludes the maximum: the long's own TTFT is
    # justifiably late under sjf/aged (it yields to the shorts) and
    # must not masquerade as the shorts' tail — p95 is the protected
    # SHORT population's number under every policy. Slots sized AT
    # the concurrency so shorts admit instantly and their TTFT
    # measures chunk-QUEUE structure, not slot starvation (which hits
    # every policy identically) — the r13 TTFT-cell sizing rule.
    n_req = 24 if tiny else 40
    long_len = 64 if tiny else 128
    max_new = 8 if tiny else 16
    slots = n_req
    ps = 8
    # near-burst arrivals: the pathology needs shorts to actually
    # overlap a long's chunk train — long prompts FIRST in the stream,
    # so under fifo every co-admitted short queues behind the whole
    # train (the r13 symmetric-mesh regime, single-host edition)
    rate = 2000.0 if tiny else 400.0
    lens = [8] * n_req
    lens[0] = long_len
    pps = -(-(max(lens) + max_new) // ps)
    net = build_model(tiny)
    trace = make_trace(n_req, [lens[i] for i in range(n_req)],
                       max_new, rate, seed=11)

    policies = ["fifo", "sjf", "aged-sjf"]
    engines = {}
    warm = make_trace(max(2, slots), (8, long_len), max_new, 1e9,
                      seed=1)
    for pol in policies:
        eng = build_engine(net, slots, ps, pps, prefill_chunk=ps,
                           scheduler=pol)
        run_engine(eng, [(0.0, p, m) for _, p, m in warm])
        eng.pool.drop_prefix_cache()
        eng.reset_results()
        eng.chunk_waits_ms.clear()     # measured reps only
        engines[pol] = eng
    # reps run INTERLEAVED across policies and the headline is the
    # MEDIAN over per-rep PAIRED ratios (events-overhead precedent):
    # this box's per-rep tick speed swings more than the structural
    # effect, and min-/max-of-reps per cell compares each cell's
    # luckiest rep against another cell's — paired ratios cancel the
    # drift instead
    reps = max(1, args.reps)
    per = {pol: {"p50": [], "p95": [], "tps": [],
                 "budget_cuts": 0, "aged_promotions": 0,
                 "preemptions": 0} for pol in policies}
    watched = ("serving/budget_cuts", "serving/aged_promotions",
               "serving/preemptions")
    from paddle_tpu.profiler import registry

    profiler.enable()
    for _ in range(reps):
        for pol, eng in engines.items():
            eng.pool.drop_prefix_cache()
            c0 = {k: registry().counter(k).value for k in watched}
            toks, wall, ttfts, _, _ = run_engine(eng, trace)
            eng.reset_results()
            per[pol]["tps"].append(toks / wall)
            per[pol]["p50"].append(pct(ttfts, 50))
            per[pol]["p95"].append(pct(ttfts, 95))
            for k in watched:
                per[pol][k.split("/")[1]] += int(
                    registry().counter(k).value - c0[k])
    summ = profiler.disable()

    def med(xs):
        return float(np.median(xs))

    cells = {}
    for pol in policies:
        # per-ENGINE chunk-wait samples (each policy is its own
        # engine, so its deque is per-policy across all its reps —
        # the registry histogram is global across the interleaved
        # cells and carries no policy signal)
        cells[pol] = {
            "policy": pol,
            "tokens_per_sec": round(med(per[pol]["tps"]), 2),
            "ttft_p50_ms": round(med(per[pol]["p50"]), 2),
            "ttft_p95_ms": round(med(per[pol]["p95"]), 2),
            "chunk_wait_p95_ms": round(
                pct(list(engines[pol].chunk_waits_ms), 95), 2),
            "budget_cuts": per[pol]["budget_cuts"],
            "aged_promotions": per[pol]["aged_promotions"],
            "preemptions": per[pol]["preemptions"],
        }
    ratio = med([f / max(a, 1e-9) for f, a in
                 zip(per["fifo"]["p95"], per["aged-sjf"]["p95"])])
    tps_ratio = med([a / max(f, 1e-9) for f, a in
                     zip(per["fifo"]["tps"], per["aged-sjf"]["tps"])])
    return {
        "metric": "serving_sched_policy_ttft_speedup",
        "value": round(ratio, 4),
        "unit": "x lower p95 TTFT, aged-sjf vs fifo chunk selection "
                "(long-prompt-mixed workload, single host)",
        "extra": {
            "mode": "tiny" if tiny else "full",
            "model": {"hidden": net.config.hidden_size,
                      "layers": net.config.num_layers,
                      "vocab": net.config.vocab_size},
            "requests": n_req, "slots": slots,
            "prompt_lens": sorted(set(lens)), "max_new": max_new,
            "arrival_rate_hz": rate, "page_size": ps,
            "prefill_chunk": ps, "reps": reps,
            "sched_cells": cells,
            "tokens_per_sec_aged_over_fifo": round(tps_ratio, 4),
            "per_rep_p95_ms": {p: [round(x, 2) for x in
                                   per[p]["p95"]] for p in policies},
            "registry": summ["metrics"],
            "note": ("mostly-8-token traffic + a couple of very long "
                     "prompts; chunk budget 1/tick so a long prompt "
                     "is a long chunk TRAIN. fifo opens chunks "
                     "oldest-admission-first: every short admitted "
                     "behind a long waits for the whole train (the "
                     "BENCH_SERVE_r13 parked-shorts pathology, "
                     "single-host edition). sjf/aged-sjf interleave "
                     "shorts ahead; aged-sjf additionally bounds the "
                     "long's own wait (serving/aged_promotions "
                     "counts the promotions; the starvation bound is "
                     "pinned in tests/test_sched.py). Outputs are "
                     "bitwise identical per request across all three "
                     "policies — only the interleaving moves — so "
                     "the TTFT delta is pure scheduling structure, "
                     "valid on CPU wall clocks; headline and tokens/s "
                     "ratio are MEDIANS of per-rep paired ratios "
                     "(interleaved reps — per_rep_p95_ms carries the "
                     "raw arms)"),
        },
    }


def build_position_fenced_draft(net, fence):
    """A draft that IS the target below position ``fence`` and is
    effectively independent beyond it: full weight copy, then the
    positional-embedding rows >= fence are re-randomized. A request
    whose positions stay under the fence sees draft == target exactly
    (twin regime, ~100% acceptance); a request past the fence
    diverges immediately (~chance acceptance). One draft model, two
    accept-rate populations co-resident — the mixed-accept workload
    adaptive spec-k exists for."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT

    d = GPT(net.config)
    d.eval()
    for (_, dp), (_, sp) in zip(d.named_parameters(),
                                net.named_parameters()):
        dp.set_value(sp)
    w = np.array(d.embeddings.wpe.weight.numpy())
    rng = np.random.RandomState(123)
    w[fence:] = (rng.randn(*w[fence:].shape) * 0.2).astype(w.dtype)
    d.embeddings.wpe.weight.set_value(paddle.to_tensor(w))
    return d


def bench_adaptive_k(args, tiny):
    """Adaptive vs static spec-k on a mixed-accept-rate workload
    (ISSUE 15): half the requests live BELOW a position fence where
    the draft is the target's twin (accept ~1.0), half start beyond
    it where the draft is effectively independent (accept ~0) — both
    populations co-resident in one engine. Static k pays full-width
    verify rows and draft ticks for the hopeless slots forever;
    adaptive k decays them to depth 0 (plain decode rows, no draft
    dispatch) while the twin slots keep full depth. Outputs are
    asserted BITWISE equal between the arms (the acceptance
    invariant is depth-independent); best-of ``--reps`` per arm,
    interleaved."""
    import paddle_tpu as paddle
    import paddle_tpu.profiler as profiler
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.profiler import registry
    from paddle_tpu.serving import (ServingConfig, ServingEngine,
                                    SpecConfig)

    k = args.draft_k
    slots = 4 if tiny else args.slots
    fence = 32 if tiny else 64
    short_len, long_len = 8, fence + 16
    # decode-heavy: the twin population must stay under the fence
    # (short_len + max_new <= fence) while the other population pays
    # many decode ticks — that is where static k's wasted verify
    # width and draft ticks accumulate
    max_new = 16 if tiny else 24
    n_req = 2 * slots
    ps = 8
    pps = -(-(long_len + max_new) // ps)

    paddle.seed(0)
    net = GPT(GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                        num_heads=4, max_seq_len=256,
                        initializer_range=0.2))
    net.eval()
    draft = build_position_fenced_draft(net, fence)
    lens = [short_len if i % 2 == 0 else long_len
            for i in range(n_req)]
    trace = make_trace(n_req, lens, max_new, 1e9, seed=13)

    def make_eng(adaptive):
        return ServingEngine(net, ServingConfig(
            num_slots=slots, page_size=ps, pages_per_slot=pps,
            scheduler=args.sched_policy,
            spec=SpecConfig(draft_model=draft, k=k,
                            adaptive=adaptive)))

    engines = {"static": make_eng(False), "adaptive": make_eng(True)}
    warm = make_trace(max(2, slots), (short_len, long_len), max_new,
                      1e9, seed=1)
    profiler.enable()
    for eng in engines.values():
        run_engine(eng, [(0.0, p, m) for _, p, m in warm])
        eng.pool.drop_prefix_cache()
        eng.reset_results()
    arms = {}
    outs = {}
    for name, eng in engines.items():
        arms[name] = {"tokens_per_sec": 0.0}
    for _ in range(max(1, args.reps)):
        for name, eng in engines.items():
            eng.pool.drop_prefix_cache()
            t0 = registry().counter("serving/ticks").value
            d0 = registry().counter("serving/spec_drafted_tokens").value
            a0 = registry().counter(
                "serving/spec_accepted_tokens").value
            toks, wall, *_ = run_engine(eng, trace)
            res = {r.prompt.tobytes(): list(r.out)
                   for r in eng._requests.values() if r.done}
            eng.reset_results()
            drafted = int(registry().counter(
                "serving/spec_drafted_tokens").value - d0)
            if toks / wall > arms[name]["tokens_per_sec"]:
                outs[name] = res
                arms[name] = {
                    "tokens_per_sec": round(toks / wall, 2),
                    "drafted_tokens": drafted,
                    "accepted_tokens": int(registry().counter(
                        "serving/spec_accepted_tokens").value - a0),
                    "verify_ticks": int(registry().counter(
                        "serving/ticks").value - t0),
                }
    assert outs["static"] == outs["adaptive"], \
        "adaptive-k output diverged from static-k greedy"
    for arm in arms.values():
        arm["accept_rate"] = round(
            arm["accepted_tokens"] / max(arm["drafted_tokens"], 1), 4)
    summ = profiler.disable()
    speedup = arms["adaptive"]["tokens_per_sec"] / \
        max(arms["static"]["tokens_per_sec"], 1e-9)
    return {
        "metric": "serving_adaptive_spec_k_speedup",
        "value": round(speedup, 4),
        "unit": "x tokens/s, adaptive vs static spec-k "
                "(mixed-accept-rate workload, greedy)",
        "extra": {
            "mode": "tiny" if tiny else "full",
            "model": {"hidden": net.config.hidden_size,
                      "layers": net.config.num_layers,
                      "vocab": net.config.vocab_size},
            "draft": {"kind": "position-fenced twin", "fence": fence,
                      "k": k},
            "slots": slots, "requests": n_req,
            "prompt_lens": sorted(set(lens)), "max_new": max_new,
            "page_size": ps, "reps": max(1, args.reps),
            "sched_policy": args.sched_policy,
            "mixed_accept": {**arms, "speedup": round(speedup, 4)},
            "registry": summ["metrics"],
            "note": ("one draft, two accept-rate populations: below "
                     "the positional fence the draft is the target's "
                     "twin (accept ~1), past it the re-randomized "
                     "positional rows make it effectively independent "
                     "(accept ~0) — twin-draft slots and "
                     "independent-draft slots co-resident. Static k "
                     "keeps drafting for the hopeless slots (k+1-wide "
                     "verify rows + draft ticks, ~1 emitted token per "
                     "tick); the adaptive controller decays them to "
                     "depth 0 — plain decode rows, and once every "
                     "resident slot is decayed the draft tick stops "
                     "dispatching entirely — while twin slots keep "
                     "full depth. Outputs bitwise equal between arms "
                     "(asserted); best-of-reps interleaved; the "
                     "adaptive arm's lower drafted_tokens at matched "
                     "accepted output is the controller's direct "
                     "evidence"),
        },
    }


def bench_multihost(args, tiny):
    """Multi-host serving (ISSUE 13): aggregate tokens/s scaling from
    1 to ``--hosts`` REAL processes on the CPU mesh, plus the
    disaggregated-vs-symmetric p95 TTFT comparison on a
    long-prompt-mixed workload.

    HONEST CPU-MESH CAVEATS (the headline's fine print): this
    container has ONE CPU core, so N timesharing processes cannot add
    compute and the WALL-clock aggregate is physically pinned near
    1.0x (reported as ``wall_scaling`` — expect ~0.9x after consensus
    and channel overhead). The headline is therefore the
    PARALLEL-HARDWARE PROJECTION: each rank measures its own CPU
    seconds over the measured window (all threads), and
    ``tokens / max(per-rank CPU)`` is the aggregate rate N actual
    cores/hosts would realize running the same rank workloads
    concurrently — a measured quantity (the ranks' real, sharded
    work), not a model; only the "they run in parallel" step is
    projected. The mesh is sharded the way the tentpole says: the
    1-host cell runs the GLOBAL engine (all slots, the whole pool),
    the N-host cell shards slots AND pages across ranks, so per-rank
    ticks genuinely shrink (a fixed-shape tick pays its full
    row-capacity FLOPs regardless of occupancy — identical per-host
    configs would burn the savings as padding). The TTFT cell runs
    both 2-host topologies at matched ample capacity, so its
    comparison is pure scheduling structure, valid even on one core
    and on wall clocks."""
    import numpy as np

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import tempfile

    import mp_mesh

    hosts = args.hosts
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "serve_worker.py")
    # full mode uses the compute-per-token model (bench_poisson's full
    # sizing): on tiny models Python/dispatch overhead swamps the
    # sharded-tick FLOPs the scaling cell measures
    model = ({"vocab": 128, "hidden": 64, "layers": 4, "heads": 4,
              "max_seq_len": 128} if tiny else
             {"vocab": 512, "hidden": 256, "layers": 6, "heads": 8,
              "max_seq_len": 192})

    def run_cell(name, world, cell_cfg, sink_root=None):
        root = tempfile.mkdtemp(prefix=f"serve_mh_{name}_")
        cfg = dict(cell_cfg, world=world, model=model,
                   shared_dir=os.path.join(root, "shared"))
        if sink_root:
            cfg["sink_dir"] = sink_root
        cfg_path = os.path.join(root, "config.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        res = mp_mesh.launch(world, worker, [cfg_path, root],
                             log_dir=os.path.join(root, "logs"),
                             timeout=cfg.get("timeout_s", 600) + 120)
        if not res.ok:
            raise SystemExit(f"multihost cell {name} failed:\n"
                             f"{res.tail()}")
        stats = []
        for r in range(world):
            with open(os.path.join(root, f"bench.{r}.json")) as f:
                stats.append(json.load(f))
        tokens = sum(s["tokens"] for s in stats)
        wall = max(s["end_w"] for s in stats) - \
            min(s["start_w"] for s in stats)
        cpus = [s["cpu_s"] for s in stats]
        ttfts = [v for s in stats for v in s["ttft_ms"].values()]
        uncs = [v for s in stats
                for v in s.get("ttft_unc_ms", {}).values()]
        served = sorted(g for s in stats for g in s["served"])
        assert served == list(range(cfg["n_requests"])), \
            f"cell {name}: served {len(served)}/{cfg['n_requests']}"
        extra_keys = {}
        if sink_root:
            extra_keys["sink_root"] = sink_root
        if uncs:
            extra_keys["ttft_unc_p95_ms"] = round(pct(uncs, 95), 3)
        return {
            **extra_keys,
            "world": world,
            "tokens": tokens,
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(tokens / wall, 2),
            "cpu_s_per_rank": cpus,
            "projected_tokens_per_sec": round(tokens / max(cpus), 2),
            "ttft_p50_ms": round(pct(ttfts, 50), 2),
            "ttft_p95_ms": round(pct(ttfts, 95), 2),
            "handoffs": sum(s["handoffs_sent"] for s in stats),
            "handoff_bytes": int(sum(s["handoff_bytes_out"]
                                     for s in stats)),
            "preemptions": int(sum(s["preemptions"] for s in stats)),
            "prefill_chunks": int(sum(s["prefill_chunks"]
                                      for s in stats)),
            "prefix_evictions": int(sum(s["prefix_evictions"]
                                        for s in stats)),
        }

    # ---- cell 1: mixed-Poisson scaling, global engine vs the pool
    # SHARDED over the mesh (slots and pages split across ranks, so a
    # rank's fixed-shape tick genuinely shrinks with its shard) ------
    ps = 8
    max_new = 24 if tiny else 48
    plens = (16, 32, 48) if tiny else (32, 48, 64)
    pps = -(-(max(plens) + max_new) // ps)
    # global slot capacity scales with the mesh so each host's shard
    # keeps >= 4 slots (below that the shard tick degenerates and the
    # scaling headline would be measured on a toy); tiny vs full scale
    # through the model + token counts instead
    g_slots = max(8, 4 * hosts)
    shard = g_slots // hosts

    def scale_cfg(slots):
        return {
            "seed": 7, "rate": 500.0,
            "n_requests": 2 * g_slots,
            "prompt_lens": list(plens), "max_new": max_new,
            "prefill_ranks": [],
            "engine": {"num_slots": slots, "page_size": ps,
                       "pages_per_slot": pps,
                       "num_pages": slots * pps + 1,
                       "prefill_chunk": ps},
            "timeout_s": 900,
        }

    cells = {"scale_1host": run_cell("s1", 1, scale_cfg(g_slots))}
    cells[f"scale_{hosts}host_symmetric"] = run_cell(
        f"s{hosts}", hosts, scale_cfg(shard))
    c1, cn = cells["scale_1host"], \
        cells[f"scale_{hosts}host_symmetric"]
    scaling = cn["projected_tokens_per_sec"] \
        / max(c1["projected_tokens_per_sec"], 1e-9)
    wall_scaling = cn["tokens_per_sec"] / max(c1["tokens_per_sec"],
                                              1e-9)

    # ---- cell 2: long-prompt-mixed TTFT, disagg vs symmetric -------
    # matched AMPLE capacity on both topologies: the delta is pure
    # scheduling structure (where long prefills run), fair on one
    # core. Mostly-short traffic + a couple of very long prompts:
    # chunked prefill is OLDEST-ADMISSION-FIRST, so on a symmetric
    # host every short admitted behind a long waits for the long's
    # ENTIRE chunk train before its own prefill starts — the
    # disaggregated decode rank never carries those chunks at all.
    # p95 (nearest-rank) over n requests must land on the SHORT
    # population (the protected one), so n >> #longs.
    # slots sized ABOVE the short concurrency so shorts admit
    # instantly and their TTFT measures chunk-queue structure, not
    # slot starvation (which would hit both topologies identically)
    n_ttft = 20 if tiny else 40
    long_len = 64 if tiny else 128
    t_max_new = 8 if tiny else 16
    long_lens = [8] * n_ttft
    long_lens[2] = long_len
    if not tiny:
        long_lens[n_ttft // 2] = 96
    lpps = -(-(max(long_lens) + t_max_new) // ps)
    ttft_cfg = {
        # arrivals the decode mesh can keep up with: short TTFT then
        # measures chunk-queue structure, not saturation backlog
        "seed": 11, "rate": 100.0 if tiny else 25.0,
        "n_requests": n_ttft,
        "prompt_lens": list(long_lens), "max_new": t_max_new,
        "prefill_ranks": [],
        "engine": {"num_slots": 8 if tiny else 16, "page_size": ps,
                   "pages_per_slot": lpps,
                   "prefill_chunk": ps},
        "long_prompt_threshold": 4 * ps,
        "timeout_s": 900,
    }
    cells["ttft_symmetric"] = run_cell("tsym", 2, ttft_cfg)
    disagg_cfg = dict(ttft_cfg, prefill_ranks=[1])
    # the disagg cell's per-rank sinks feed the cross-host trace
    # merger (ISSUE 14); with --sink-dir the rank dirs land at a
    # stable path so CI can re-run tools/merge_traces.py over them
    tdis_sink = os.path.join(args.sink_dir, "mh_tdis") \
        if args.sink_dir else tempfile.mkdtemp(prefix="serve_mh_sink_")
    cells["ttft_disagg"] = run_cell("tdis", 2, disagg_cfg,
                                    sink_root=tdis_sink)
    ttft_ratio = cells["ttft_disagg"]["ttft_p95_ms"] / \
        max(cells["ttft_symmetric"]["ttft_p95_ms"], 1e-9)

    # ---- merged cross-host trace (ISSUE 14): stitch the disagg
    # cell's per-rank sinks into ONE clock-aligned timeline per
    # request — the true end-to-end TTFT (with its uncertainty) and
    # the handoff breakdown the PR 13 caveat said were unmeasurable --
    import merge_traces

    mdoc = merge_traces.merge(tdis_sink)
    mpath = os.path.join(tdis_sink, "merged_trace.json")
    with open(mpath + ".tmp", "w") as f:
        json.dump(mdoc, f)
    os.replace(mpath + ".tmp", mpath)
    merged_block = {
        "artifact": mpath,
        "partial": mdoc["partial"],
        "requests_total": mdoc["requests_total"],
        "requests_complete": mdoc["requests_complete"],
        "handoffs": mdoc["handoffs"],
        "monotonic_violations": mdoc["monotonic_violations"],
        "ranks": mdoc["ranks"],
        "e2e_ttft_ms": mdoc["latency"]["ttft_ms"],
        "e2e_ttft_unc_ms": mdoc["latency"]["ttft_unc_ms"],
        "handoff_breakdown_ms": mdoc["handoff_breakdown_ms"],
    }

    return {
        "metric": "serving_multihost_scaling",
        "value": round(scaling, 4),
        "unit": f"x aggregate tokens/s, 1 -> {hosts} real processes "
                "(mixed Poisson; parallel-hardware projection from "
                "measured per-rank CPU seconds — see note)",
        "extra": {
            "mode": "tiny" if tiny else "full",
            "hosts": hosts, "model": model,
            "cells": cells,
            "wall_scaling": round(wall_scaling, 4),
            "ttft_p95_disagg_over_symmetric": round(ttft_ratio, 4),
            "merged_trace": merged_block,
            "scale_workload": {
                k: scale_cfg(g_slots)[k] for k in
                ("n_requests", "prompt_lens", "max_new", "engine")},
            "shard_slots": shard,
            "ttft_workload": {
                k: ttft_cfg[k] for k in
                ("n_requests", "prompt_lens", "max_new", "engine",
                 "long_prompt_threshold")},
            "note": ("ONE-CORE CPU container: N timesharing "
                     "processes cannot add compute, so the honest "
                     "WALL aggregate (extra.wall_scaling) is pinned "
                     "near 1.0x minus consensus/channel overhead — "
                     "that is container physics, not the runtime. "
                     "The headline divides total served tokens by "
                     "the MAX of the measured per-rank CPU seconds "
                     "(all threads, measured-window delta): the "
                     "rank workloads and their costs are fully "
                     "measured and genuinely sharded (slots AND "
                     "pages split per rank, so each rank's "
                     "fixed-shape tick is proportionally smaller); "
                     "only the final 'ranks run concurrently' step "
                     "is projected, which is what separate hosts "
                     "do by construction. Consensus admission, the "
                     "done-agreement rounds, and KV-handoff bytes "
                     "all ride the measured window. The TTFT cell "
                     "is pure wall clock and needs no projection: "
                     "2-host disaggregated (rank 1 absorbs long "
                     "prompts' chunk trains; rank 0 keeps the "
                     "decode-only fast path + short prefills — "
                     "chunk selection is oldest-admission-first, so "
                     "a symmetric host parks every short behind a "
                     "long's whole chunk train) vs 2-host symmetric "
                     "at matched ample capacity. Since ISSUE 14, a "
                     "handed-off request's TTFT is the TRUE "
                     "end-to-end number — prefill-rank submit to "
                     "decode-rank first token, clock-offset-"
                     "corrected with a stated uncertainty (cell "
                     "ttft_unc_p95_ms; per-request bounds in "
                     "extra.merged_trace) — replacing PR 13's "
                     "prefill-side same-host pairs, which priced "
                     "the handoff at zero by construction. "
                     "extra.merged_trace is derived by "
                     "tools/merge_traces.py from the disagg cell's "
                     "per-rank sinks: export / channel-wait / "
                     "import ms are measured spans of the same "
                     "stitched timelines."),
        },
    }


def bench_elastic(args, tiny):
    """Elastic serving mesh (ISSUE 17): what a mid-run rank death
    costs the re-dispatched tail. Two cells on REAL processes (env-
    protocol ranks, no jax.distributed — its fatal poller would abort
    the survivors), same 3-rank symmetric mesh, same seeded Poisson
    trace:

      undisturbed   all three ranks serve to completion
      kill_one      rank 2 ``os._exit(137)``s once the clock passes
                    die_after_s while it holds unserved assigned work
                    (a real corpse with real orphans); the survivors
                    detect the stale lease, agree the member out, and
                    re-dispatch every orphan through the normal router

    Headline: p95 TTFT of the kill cell's RE-DISPATCHED gids over the
    undisturbed cell's p95 — the orphaned tail pays one dead-rank
    detection window (~2x lease) plus a fresh prefill, and this cell
    prices exactly that. Zero-loss is asserted, not assumed: the
    survivors' served sets must union to every submitted gid, exactly
    once. Valid on CPU wall clocks: both cells timeshare the same
    core, and the headline compares tails across cells of the SAME
    workload, so the delta is detection + re-dispatch structure."""
    import tempfile

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import mp_mesh

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "serve_worker.py")
    world = 3
    n_req = 18 if tiny else 36
    max_new = 16 if tiny else 24
    rate = 4.0 if tiny else 6.0
    plens = (8, 16, 12) if tiny else (16, 32, 24)
    ps = 8
    slots = 4
    pps = -(-(max(plens) + max_new) // ps)
    lease_s = 1.0
    # arrivals span n_req/rate seconds; dying ~a third of the way in
    # guarantees pending work on the corpse AND a long survivor tail
    die_after_s = (n_req / rate) / 3.0
    model = {"vocab": 128, "hidden": 64, "layers": 4, "heads": 4,
             "max_seq_len": 128}

    def run_cell(name, die):
        root = tempfile.mkdtemp(prefix=f"serve_el_{name}_")
        cfg = {
            "seed": 7, "rate": rate, "n_requests": n_req,
            "prompt_lens": list(plens), "max_new": max_new,
            "prefill_ranks": [], "world": world, "model": model,
            "shared_dir": os.path.join(root, "shared"),
            "engine": {"num_slots": slots, "page_size": ps,
                       "pages_per_slot": pps, "prefill_chunk": ps},
            "env_only": True, "lease_s": lease_s,
            "timeout_s": 600,
        }
        if die:
            cfg["die_rank"] = world - 1
            cfg["die_after_s"] = die_after_s
        cfg_path = os.path.join(root, "config.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        res = mp_mesh.launch(
            world, worker, [cfg_path, root],
            log_dir=os.path.join(root, "logs"), timeout=720,
            expect_fail_ranks=(world - 1,) if die else ())
        if not res.ok:
            raise SystemExit(f"elastic cell {name} failed:\n"
                             f"{res.tail()}")
        ranks = range(world - 1) if die else range(world)
        stats = []
        for r in ranks:
            with open(os.path.join(root, f"bench.{r}.json")) as f:
                stats.append(json.load(f))
        served = sorted(g for s in stats for g in s["served"])
        assert served == list(range(n_req)), \
            f"cell {name}: lost/duplicated requests " \
            f"({len(served)} served of {n_req})"
        ttfts = {g: v for s in stats
                 for g, v in s["ttft_ms"].items()}
        redis = {g: m for s in stats
                 for g, m in s["redispatched"].items()}
        return {
            "stats": stats, "ttft_ms": ttfts, "redispatched": redis,
            "members": stats[0]["members"],
        }

    undis = run_cell("undisturbed", die=False)
    kill = run_cell("kill_one", die=True)

    assert not undis["redispatched"], "undisturbed cell re-dispatched"
    assert kill["redispatched"], \
        "the corpse held nothing — no re-dispatched tail to price"
    assert kill["members"] == [0, 1], kill["members"]

    undis_all = list(undis["ttft_ms"].values())
    tail = [kill["ttft_ms"][g] for g in kill["redispatched"]
            if g in kill["ttft_ms"]]
    assert len(tail) == len(kill["redispatched"]), \
        "a re-dispatched gid finished without a TTFT"
    rest = [v for g, v in kill["ttft_ms"].items()
            if g not in kill["redispatched"]]
    undis_p95 = pct(undis_all, 95)
    tail_p95 = pct(tail, 95)
    inflation = tail_p95 / max(undis_p95, 1e-9)

    def cell_block(c, die):
        ranks = (0, 1) if die else (0, 1, 2)
        return {
            "world": world, "ranks_finished": list(ranks),
            "tokens": sum(s["tokens"] for s in c["stats"]),
            "ttft_p50_ms": round(pct(list(c["ttft_ms"].values()), 50),
                                 2),
            "ttft_p95_ms": round(pct(list(c["ttft_ms"].values()), 95),
                                 2),
            "handoffs": sum(s["handoffs_sent"] for s in c["stats"]),
            "redispatched": len(c["redispatched"]),
            "members": c["members"],
        }

    modes = {}
    for m in kill["redispatched"].values():
        modes[m] = modes.get(m, 0) + 1
    return {
        "metric": "serving_elastic_redispatch_ttft_inflation",
        "value": round(inflation, 4),
        "unit": "x p95 TTFT, kill-one cell's re-dispatched tail vs "
                "the undisturbed mesh (same workload, zero lost)",
        "extra": {
            "mode": "tiny" if tiny else "full",
            "model": model, "world": world,
            "requests": n_req, "max_new": max_new,
            "prompt_lens": list(plens), "arrival_rate_hz": rate,
            "page_size": ps, "slots_per_rank": slots,
            "lease_s": lease_s, "die_after_s": round(die_after_s, 2),
            "die_rank": world - 1,
            "cells": {"undisturbed": cell_block(undis, False),
                      "kill_one": cell_block(kill, True)},
            "redispatched_tail": {
                "count": len(tail),
                "modes": modes,
                "ttft_p50_ms": round(pct(tail, 50), 2),
                "ttft_p95_ms": round(tail_p95, 2),
            },
            "kill_undisturbed_requests_ttft_p95_ms": round(
                pct(rest, 95), 2) if rest else None,
            "undisturbed_ttft_p95_ms": round(undis_p95, 2),
            "note": ("zero-loss asserted in BOTH cells: every "
                     "submitted gid finished on exactly one "
                     "surviving rank. The re-dispatched tail pays "
                     "the dead-rank detection window (lease_s-based, "
                     "~2x lease) plus a fresh prefill (or a "
                     "scavenged-KV import when the corpse's export "
                     "survived and audits clean) — the inflation "
                     "prices exactly that recovery path. Env-"
                     "protocol ranks (no jax.distributed): the "
                     "coordination service's fatal poller would "
                     "abort the survivors ~100 s after the kill, "
                     "which is the opposite of elastic"),
        },
    }


def bench_prefix_routing(args, tiny):
    """Global KV economy (ISSUE 18): prefix-affinity routing + hot-
    chain migration vs the affinity-BLIND mesh, on 2 REAL processes
    over a shared-system-prompt tenant workload.

    Three tenants, each with its own system prompt, interleaved with
    a deliberate skew (tenant 0 sends half the traffic): every rank
    publishes digest chains of its cached prefixes through the board,
    the router prices a published prefix hit against the load vote,
    and when load overrides affinity the hot chain's pages MIGRATE to
    the loaded-onto rank (int8 scales travel with the pages). The
    affinity-blind arm is the same mesh with ``prefix_routing`` off —
    local prefix caching still on, so the delta prices the ECONOMY
    (placement + migration), not caching itself.

    Headline: paired-median over interleaved reps of
    ``blind mean TTFT / affinity mean TTFT`` (PR 15 precedent: pairing
    and interleaving cancel the container's timeshared-CPU drift).
    Correctness is asserted in-run, not assumed: every cell must serve
    every gid exactly once, and every f32 cell's full decoded
    sequences must be BITWISE equal to dense ``generate()`` references
    the driver computes itself — routing and migration move placement,
    never tokens. A final affinity cell at ``kv_dtype='int8'`` prices
    migration bytes by dtype (quantized pages ship ~4x fewer payload
    bytes + their per-page per-head scales); int8 is outside the
    bitwise contract (PR 12) so that cell skips the dense check."""
    import tempfile

    import numpy as np

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import mp_mesh

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "serve_worker.py")
    world = 2
    tenants = 5
    sys_len = 48 if tiny else 96
    # a suffix SHORTER than one page: only full pages are indexed, so
    # the trie holds exactly the shared system chains — a page-sized
    # suffix would index every request's unique tail page, polluting
    # the pool until nothing else fits (least of all a migrated chain,
    # whose import refuses to evict)
    sfx_len = 7
    # prefill-dominated requests (long system prompt, SHORT decodes)
    # at a rate service can keep up with: TTFT is then prefill chunks
    # + small queue waits, the term the economy actually moves.
    # Single-slot ranks keep the over-penalty live — any arrival
    # overlap queues, the router spills the hot tenant, and the spill
    # fires migration EARLY enough that later hot-tenant arrivals
    # route against the replicated chain (an overloaded mesh routes
    # its whole trace before the first migration completes — the r18
    # tuning trap; and long decodes make queue waits, which affinity
    # concentration amplifies, swamp the prefill savings).
    max_new = 6 if tiny else 8
    n_req = 24 if tiny else 40
    rate = 16.0 if tiny else 8.0
    ps = 8
    # routing chunk COARSER than the page: the affinity discount
    # (hit tokens // chunk) then prices BELOW one queued request's
    # over-penalty, so the router abandons the affine rank the moment
    # a real queue forms instead of tolerating standing queue depth
    # whose wait dwarfs the saved prefill
    chunk = 16
    slots = 1
    pps = -(-(sys_len + sfx_len + max_new) // ps)
    # pool sized so a rank can cache ITS tenants' system chains PLUS
    # one migrated hot chain (imports use the non-evicting allocator
    # — no room means the chain is dropped, honestly) but not
    # everyone's: the blind arm spreads all 5 tenants across both
    # ranks and pays chain eviction + full re-prefill; the affinity
    # arm's tenant partition fits. That capacity asymmetry is the
    # economy's edge, and it is priced in pages, not assumed.
    num_pages = slots * pps + (24 if tiny else 48) + 1
    # tenant 0 is hot AND bursty (back-to-back doubles): the second
    # T0 of a double arrives while its affine rank still decodes the
    # first, so that rank's live vote shows the slot busy, the
    # over-penalty beats the affinity discount, the request spills —
    # and the spill drags the chain across via migration, after which
    # BOTH ranks serve tenant 0 with hits (the dst's are the
    # cross-rank remote hits the acceptance gate counts)
    pattern = [0, 0, 1, 2, 0, 0, 3, 4]
    lease_s = 1.0
    model = {"vocab": 128, "hidden": 64, "layers": 4, "heads": 4,
             "max_seq_len": 128} if tiny else \
            {"vocab": 256, "hidden": 128, "layers": 4, "heads": 4,
             "max_seq_len": 192}
    reps = 1 if tiny else max(2, args.reps)

    # ---- the driver replays the workers' trace RNG (systems first,
    # then per-request gap + suffix) and computes dense references —
    # the parity oracle no serving-side bug can also infect ----------
    def tenant_trace(seed):
        rng = np.random.RandomState(seed)
        systems = [rng.randint(0, 128, (sys_len,)).astype(np.int32)
                   for _ in range(tenants)]
        out = []
        t = 0.0
        for i in range(n_req):
            t += float(rng.exponential(1.0 / rate))
            sfx = rng.randint(0, 128, (sfx_len,)).astype(np.int32)
            out.append(np.concatenate(
                [systems[pattern[i % len(pattern)]], sfx]))
        return out

    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, GPTConfig

    paddle.seed(0)
    net = GPT(GPTConfig(vocab_size=model["vocab"],
                        hidden_size=model["hidden"],
                        num_layers=model["layers"],
                        num_heads=model["heads"],
                        max_seq_len=model["max_seq_len"],
                        initializer_range=0.2))
    net.eval()
    prompts = tenant_trace(seed=7)
    refs = {}
    for g, p in enumerate(prompts):
        ids, _ = net.generate(paddle.to_tensor(p[None]),
                              max_new_tokens=max_new)
        refs[g] = [int(x) for x in ids.numpy()[0]]

    def run_cell(name, affinity, kv=None, sink_root=None,
                 verify=True):
        root = tempfile.mkdtemp(prefix=f"serve_px_{name}_")
        eng_cfg = {"num_slots": slots, "page_size": ps,
                   "pages_per_slot": pps, "num_pages": num_pages,
                   "prefill_chunk": chunk}
        if kv:
            eng_cfg["kv_dtype"] = kv
        cfg = {
            "seed": 7, "rate": rate, "n_requests": n_req,
            "prompt_lens": [sys_len + sfx_len], "max_new": max_new,
            "tenants": {"n": tenants, "sys_len": sys_len,
                        "sfx_len": sfx_len, "pattern": pattern},
            "prefill_ranks": [], "world": world, "model": model,
            "shared_dir": os.path.join(root, "shared"),
            "engine": eng_cfg,
            "env_only": True, "lease_s": lease_s,
            "prefix_routing": bool(affinity),
            "prefix_publish_s": 0.1,
            "return_outputs": True,
            "timeout_s": 600,
        }
        if sink_root:
            cfg["sink_dir"] = sink_root
        cfg_path = os.path.join(root, "config.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        res = mp_mesh.launch(world, worker, [cfg_path, root],
                             log_dir=os.path.join(root, "logs"),
                             timeout=720)
        if not res.ok:
            raise SystemExit(f"prefix-routing cell {name} failed:\n"
                             f"{res.tail()}")
        stats = []
        for r in range(world):
            with open(os.path.join(root, f"bench.{r}.json")) as f:
                stats.append(json.load(f))
        served = sorted(g for s in stats for g in s["served"])
        assert served == list(range(n_req)), \
            f"cell {name}: lost/duplicated requests " \
            f"({len(served)} served of {n_req})"
        if verify:
            for s in stats:
                for g, seq in s["outputs"].items():
                    assert seq == refs[int(g)], \
                        f"cell {name}: gid {g} diverged from the " \
                        "dense reference on rank " \
                        f"{s['rank']} — routing/migration moved " \
                        "tokens, not just placement"
        ttfts = [v for s in stats for v in s["ttft_ms"].values()]
        px = [s["prefix"] for s in stats]
        wall = max(s["end_w"] for s in stats) - \
            min(s["start_w"] for s in stats)
        return {
            "affinity": bool(affinity),
            "kv_dtype": px[0]["kv_dtype"],
            "mean_ttft_ms": round(float(np.mean(ttfts)), 2),
            "ttft_p50_ms": round(pct(ttfts, 50), 2),
            "ttft_p95_ms": round(pct(ttfts, 95), 2),
            "tokens": sum(s["tokens"] for s in stats),
            "wall_s": round(wall, 3),
            "prefill_chunks": int(sum(s["prefill_chunks"]
                                      for s in stats)),
            "prefix_hit_tokens": sum(p["prefix_hit_tokens"]
                                     for p in px),
            "remote_hit_tokens": sum(p["remote_hit_tokens"]
                                     for p in px),
            "migrations": sum(p["migrations_out"] for p in px),
            "migration_bytes": sum(p["migration_bytes_out"]
                                   for p in px),
            "stale_withdrawals": sum(p["stale_withdrawals"]
                                     for p in px),
            "published_chains": [p["published_chains"] for p in px],
            "per_rank_hit_tokens": [p["prefix_hit_tokens"]
                                    for p in px],
            "per_rank_prefix": px,
        }

    # ---- interleaved paired reps: blind then affinity, back to back
    # per rep, so timeshared-CPU drift hits both arms of a pair ------
    aff_cells, blind_cells = [], []
    sink_root = os.path.join(args.sink_dir, "px_aff") \
        if args.sink_dir else tempfile.mkdtemp(prefix="serve_px_sink_")
    for rep in range(reps):
        blind_cells.append(run_cell(f"blind{rep}", affinity=False))
        aff_cells.append(run_cell(
            f"aff{rep}", affinity=True,
            sink_root=sink_root if rep == reps - 1 else None))
    ratios = sorted(b["mean_ttft_ms"] / max(a["mean_ttft_ms"], 1e-9)
                    for a, b in zip(aff_cells, blind_cells))
    ratio = ratios[len(ratios) // 2]

    # ---- economy evidence, asserted (the full-run artifact is the
    # acceptance gate; tiny smoke keeps the structural asserts only) -
    hit_total = sum(c["prefix_hit_tokens"] for c in aff_cells)
    remote_total = sum(c["remote_hit_tokens"] for c in aff_cells)
    migr_total = sum(c["migrations"] for c in aff_cells)
    assert hit_total > 0, \
        "affinity arm never hit a prefix — the economy did nothing"
    assert all(any(n > 0 for n in c["published_chains"])
               for c in aff_cells), "no rank ever published a digest"
    if not tiny:
        assert migr_total > 0, \
            "no hot chain ever migrated — the spill pressure the " \
            "workload skew exists to create never materialized"
        assert remote_total > 0, \
            "no cross-rank hit: migrated chains never served a " \
            "request on their new rank"

    # ---- migration bytes by dtype: one int8 affinity cell (outside
    # the bitwise contract, PR 12 — no dense check) ------------------
    int8_cell = run_cell("int8", affinity=True, kv="int8",
                         verify=False)
    bytes_by_dtype = {
        "float32": {
            "migrations": migr_total,
            "migration_bytes": sum(c["migration_bytes"]
                                   for c in aff_cells)},
        "int8": {
            "migrations": int8_cell["migrations"],
            "migration_bytes": int8_cell["migration_bytes"]},
    }

    # ---- merged cross-host trace (PR 14 merger) over the last
    # affinity rep's per-rank sinks: e2e TTFT with uncertainty -------
    import merge_traces

    mdoc = merge_traces.merge(sink_root)
    mpath = os.path.join(sink_root, "merged_trace.json")
    with open(mpath + ".tmp", "w") as f:
        json.dump(mdoc, f)
    os.replace(mpath + ".tmp", mpath)
    merged_block = {
        "artifact": mpath,
        "partial": mdoc["partial"],
        "requests_total": mdoc["requests_total"],
        "requests_complete": mdoc["requests_complete"],
        "e2e_ttft_ms": mdoc["latency"]["ttft_ms"],
        "e2e_ttft_unc_ms": mdoc["latency"]["ttft_unc_ms"],
    }

    agg = {
        "prefix_hit_tokens": hit_total,
        "remote_hit_tokens": remote_total,
        "migrations": migr_total,
        "migration_bytes_out": sum(c["migration_bytes"]
                                   for c in aff_cells),
        "stale_withdrawals": sum(c["stale_withdrawals"]
                                 for c in aff_cells),
        "kv_dtype": "float32",
    }
    return {
        "metric": "serving_prefix_economy_ttft_speedup",
        "value": round(ratio, 4),
        "unit": "x mean TTFT, affinity-blind mesh over the "
                "prefix-economy mesh (paired-median over interleaved "
                "reps; >1 = economy wins)",
        "extra": {
            "mode": "tiny" if tiny else "full",
            "model": model, "world": world,
            "tenants": tenants, "tenant_pattern": pattern,
            "system_prompt_tokens": sys_len,
            "suffix_tokens": sfx_len, "requests": n_req,
            "max_new": max_new, "arrival_rate_hz": rate,
            "page_size": ps, "slots_per_rank": slots,
            "pages_per_rank": num_pages, "lease_s": lease_s,
            "reps": reps,
            "paired_ttft_ratios": [round(r, 4) for r in ratios],
            "prefix_economy": agg,
            "migration_bytes_by_dtype": bytes_by_dtype,
            "cells": {"affinity": aff_cells, "blind": blind_cells,
                      "int8": int8_cell},
            "merged_trace": merged_block,
            "note": ("both arms run the SAME seeded tenant trace on "
                     "the same 2-process mesh with local prefix "
                     "caching ON — the blind arm differs only in "
                     "prefix_routing=False, so the headline prices "
                     "placement + migration, not caching. Every f32 "
                     "cell's full decoded sequences are asserted "
                     "bitwise-equal to dense generate() references "
                     "computed by the driver; the int8 cell prices "
                     "migration bytes at 4x pool-byte density "
                     "(PR 12's token-match contract, not bitwise) "
                     "and ships per-page per-head scales with the "
                     "pages. Digests (chain hashes + lengths) are "
                     "the ONLY thing published through the board; "
                     "page bytes move point-to-point over the "
                     "handoff channel on migrate directives. "
                     "One-core container: arms are paired and "
                     "interleaved so timeshared-CPU drift cancels "
                     "in the ratio"),
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (~2 min)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="shared-system-prompt workload: prefix-cache-on"
                         " vs -off TTFT comparison")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative decoding: spec engine (early-"
                         "exit draft, greedy acceptance) vs the plain "
                         "engine on the Poisson workload")
    ap.add_argument("--sampling", action="store_true",
                    help="with --spec-decode: sampled speculative "
                         "decoding (rejection-sampling acceptance) — "
                         "plain-sampled vs sync-absorb vs overlap "
                         "(chained draft tick) arms; sync and overlap "
                         "outputs asserted equal")
    ap.add_argument("--draft-layers", type=int, default=1,
                    help="early-exit draft depth (target blocks "
                         "copied; clamped below the target's depth)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens speculated per verify tick")
    ap.add_argument("--sched-policy", default="fifo",
                    choices=["fifo", "sjf", "aged-sjf"],
                    help="engine chunk-selection policy (ISSUE 15; "
                         "serving/sched.py) for the single-host "
                         "modes; non-fifo policies also shape the "
                         "per-tick prefill budget from decode-stall "
                         "telemetry")
    ap.add_argument("--sched-matrix", action="store_true",
                    help="run the long-prompt-mixed workload under "
                         "every chunk-selection policy (fifo / sjf / "
                         "aged-sjf): p95 TTFT + tokens/s per policy "
                         "— the parked-shorts comparison "
                         "(BENCH_SERVE_r15.json)")
    ap.add_argument("--adaptive-k", action="store_true",
                    help="adaptive vs static spec-k on a mixed-"
                         "accept-rate workload (position-fenced twin "
                         "draft: twin-accept and ~zero-accept "
                         "requests co-resident); combines with "
                         "--sched-policy")
    ap.add_argument("--kv-dtype", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="page-pool storage dtype. 'f32' runs the "
                         "normal modes; 'bf16'/'int8' switch to the "
                         "KV-quantization comparison (residency at "
                         "matched pool bytes + greedy token-match / "
                         "perplexity quality proxy vs the f32 engine, "
                         "ISSUE 12)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="run the multi-host serving comparison on N "
                         "REAL processes (tools/mp_mesh.py): 1-host "
                         "vs N-host aggregate tokens/s at fixed "
                         "per-host pool capacity, plus the 2-host "
                         "disaggregated-vs-symmetric p95 TTFT cell "
                         "(ISSUE 13) and the merged cross-host trace "
                         "block — true e2e disagg TTFT with clock "
                         "uncertainty + handoff breakdown (ISSUE 14; "
                         "BENCH_SERVE_r14.json)")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic-mesh cell (ISSUE 17): 3 real "
                         "env-protocol ranks, undisturbed vs kill-one "
                         "(rank 2 dies mid-run holding work); headline "
                         "is the re-dispatched tail's p95 TTFT over "
                         "the undisturbed mesh's, zero-loss asserted "
                         "in both cells (BENCH_SERVE_r17.json)")
    ap.add_argument("--prefix-routing", action="store_true",
                    help="global-KV-economy cell (ISSUE 18): 2 real "
                         "env-protocol ranks on a skewed shared-"
                         "system-prompt tenant workload, prefix-"
                         "affinity routing + hot-chain migration vs "
                         "the affinity-blind mesh (local caching on "
                         "in both); headline is the paired-median "
                         "blind/affinity mean-TTFT ratio, bitwise "
                         "parity to dense references asserted, plus "
                         "an int8 cell pricing migration bytes by "
                         "dtype (BENCH_SERVE_r18.json)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--reps", type=int, default=3,
                    help="repetitions per arm of a comparison (best-of)")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--sink-dir", default=None,
                    help="enable the persistent metrics sink into this "
                         "directory (metrics.jsonl + events.jsonl + "
                         "metrics.prom, final flush on exit)")
    ap.add_argument("--live-status", default=None, metavar="DIR",
                    help="run a LiveAggregator (profiler/live.py, "
                         "ISSUE 16) over DIR's telemetry frames for "
                         "the whole bench: mesh_status.json/.prom "
                         "rewritten in DIR every tick, the final "
                         "document + the measured aggregation "
                         "overhead (paired-median, Poisson mode) "
                         "attached as extra.live_status. Single-host: "
                         "pass the --sink-dir path; --hosts N: pass "
                         "the disagg cell's sink root "
                         "(<sink-dir>/mh_tdis)")
    ap.add_argument("--trace-window", type=int, default=0,
                    metavar="N",
                    help="after the measured comparison, drive N warm "
                         "engine ticks under a parsed device-trace "
                         "window and embed the per-tick device "
                         "timeline (op categories, per-collective "
                         "durations, overlap fraction, goodput/MFU "
                         "ledger) as extra.device_trace; with "
                         "--sink-dir the summary also lands as "
                         "trace_summary.json (Poisson and "
                         "--prefix-cache modes)")
    args = ap.parse_args()
    if args.sampling and not args.spec_decode:
        ap.error("--sampling qualifies --spec-decode (the sampled "
                 "rejection-acceptance cell); the plain Poisson mode "
                 "is greedy-only")
    if args.trace_window and (args.spec_decode or args.sched_matrix
                              or args.adaptive_k):
        ap.error("--trace-window rides the Poisson or --prefix-cache "
                 "modes (the matrix/spec cells stay lean)")
    if args.kv_dtype != "f32" and (args.spec_decode or
                                   args.prefix_cache or
                                   args.trace_window or
                                   args.sched_matrix or
                                   args.adaptive_k):
        ap.error("--kv-dtype bf16/int8 is its own comparison mode "
                 "(residency + quality proxy vs the f32 engine)")
    if args.sched_matrix and (args.spec_decode or args.prefix_cache
                              or args.adaptive_k):
        ap.error("--sched-matrix is its own comparison mode")
    if args.adaptive_k and (args.spec_decode or args.prefix_cache):
        ap.error("--adaptive-k is its own comparison mode (the "
                 "static-vs-adaptive spec engines are built inside)")
    if args.elastic and (args.spec_decode or args.prefix_cache or
                         args.sched_matrix or args.adaptive_k or args.kv_dtype != "f32" or
                         args.hosts > 1 or args.trace_window or
                         args.sink_dir or args.live_status):
        ap.error("--elastic is its own comparison mode (real "
                 "processes; per-cell sinks live in the cell dirs)")
    if args.prefix_routing and (
            args.spec_decode or args.prefix_cache or args.sched_matrix or
            args.adaptive_k or args.kv_dtype != "f32" or
            args.hosts > 1 or args.elastic or args.trace_window or
            args.live_status):
        ap.error("--prefix-routing is its own comparison mode (real "
                 "processes; --sink-dir feeds the merged-trace block)")

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # runs on the backend jax finds (CI legs and tests that mean the CPU
    # say JAX_PLATFORMS=cpu). The process-spawning modes are CPU
    # harnesses by construction — tools/mp_mesh.py and serve_worker.py
    # pin their children to the CPU, and a chip belongs to one process
    # — so their parent stays on the CPU as well.
    if args.hosts > 1 or args.elastic or args.prefix_routing:
        jax.config.update("jax_platforms", "cpu")

    if args.live_status and not args.sink_dir and args.hosts <= 1:
        ap.error("--live-status tails a sink's telemetry frames — "
                 "pass --sink-dir too (same directory)")

    if args.sink_dir:
        import paddle_tpu.profiler as profiler

        profiler.enable_sink(args.sink_dir, interval_s=5.0)

    live_agg = None
    if args.live_status:
        from paddle_tpu.profiler.live import LiveAggregator

        # staleness generous vs the 5s sink interval: a bench rank is
        # not dead for flushing on schedule
        live_agg = LiveAggregator(args.live_status, interval_s=1.0,
                                  staleness_s=30.0).start()

    if args.elastic:
        out = bench_elastic(args, args.tiny)
    elif args.prefix_routing:
        out = bench_prefix_routing(args, args.tiny)
    elif args.hosts > 1:
        if args.spec_decode or args.prefix_cache or args.kv_dtype != "f32" or \
                args.sched_matrix or args.adaptive_k:
            ap.error("--hosts N is its own comparison mode")
        out = bench_multihost(args, args.tiny)
    elif args.kv_dtype != "f32":
        out = bench_kv_quant(args, args.tiny)
    elif args.spec_decode:
        out = (bench_spec_sampling(args, args.tiny) if args.sampling
               else bench_spec(args, args.tiny))
    elif args.sched_matrix:
        out = bench_sched_matrix(args, args.tiny)
    elif args.adaptive_k:
        out = bench_adaptive_k(args, args.tiny)
    elif args.prefix_cache:
        out = bench_shared_prefix(args, args.tiny)
    else:
        out = bench_poisson(args, args.tiny)

    if args.sink_dir:
        import paddle_tpu.profiler as profiler

        s = profiler.active_sink()
        profiler.disable_sink("exit")   # deterministic final flush
        out.setdefault("extra", {})["sink"] = {
            "dir": args.sink_dir, "flushes": s.flushes if s else 0,
            "frames": s.frames_written if s else 0}
    if live_agg is not None:
        # stop AFTER the sink's exit flush: the final tick folds the
        # last frames in, so the attached document covers the run
        live_agg.stop()
        out.setdefault("extra", {})["live_status"] = {
            "dir": args.live_status,
            "ticks": live_agg.status["tick"] if live_agg.status
            else 0,
            "mesh_status": live_agg.status}
    from paddle_tpu.profiler.instrument import device_stamp

    out.setdefault("extra", {})["device"] = device_stamp()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
