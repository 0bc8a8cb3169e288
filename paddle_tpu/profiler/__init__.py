"""paddle_tpu.profiler — unified runtime observability.

Three always-on pieces ride alongside (ISSUE 8): per-request **event
timelines** + the **flight recorder** (events.py — serving lifecycle
edges, latency breakdowns, rolling TTFT/TPOT percentiles, post-mortem
dumps on watchdog fire/rollback), the **persistent metrics sink**
(sink.py — registry + event log as JSONL and a Prometheus textfile,
flushed on interval/exit/preempt/watchdog/rollback), and
**compiled-program accounting** (xla_stats.py — compile wall-time +
``cost_analysis()`` FLOPs/bytes per dispatch site, the inventory that
keys against recompile-telemetry names).

Device-time truth (ISSUE 11; device_trace.py): windowed
``jax.profiler.trace`` captures parsed with stdlib gzip+json into
per-op-category timings, per-collective measured durations (joined
with the byte accounting), a measured compute∩comm overlap fraction
(``phase/comm_traced_ms`` next to the apportioned
``phase/comm_measured_ms``), and a goodput/MFU ledger — via
``profiler.trace_capture`` / ``profiler.TraceWindow``,
``profile_step_phases(trace_window=k)``,
``ServingEngine.trace_window()`` and ``serve_bench --trace-window``.

**Set-up, on the program's own clock** (ISSUE 35; always on, like the
event log it writes to): ``profiler.phase("setup/...")`` records the
edges of set-up as ``phase`` events whether or not ``enable()`` is on
(trace.py): the package's import (``setup/import``; the gauge
``proc/age_at_import_s`` is the process's age before it), the trainer's
and the engine's constructors with their children (``setup/trainer``,
``setup/engine``), every dispatch site's first call
(``setup/first_call`` [``site``]). Parameters are too many for a phase
each: their making adds to the counters ``setup/weights_s{where=}``,
``setup/weights_bytes{where=}`` and ``setup/cast_s``
(``trace.charge_setup``). What each compilation cost is heard from
``jax.monitoring`` (recompile.py) and charged to the site whose first
call is open, else to ``eager``: the inventory's ``trace_s`` /
``lower_s`` / ``backend_s`` / ``cache_fetch_s`` / ``cache_hit``, the
counters ``compile/*`` and one ``compile`` event a program. Nothing of
it runs on a tick or a step.

**Every tick, on the engine's own record** (ISSUE 51; always on, because
the window it must see is the one in which ``scope`` is a no-op):
``ServingEngine.tick_log()`` / ``profiler.tick_logs()`` (ticklog.py): one
row a ``step()`` with the ``pt:step/*`` boundaries on ``perf_counter_ns``,
the interval's parts, the tick's arrival and the engine thread's own
counters (proc.py: CPU time, run-queue wait, involuntary switches, major
faults; the collector's time from one ``gc.callbacks`` entry that also
feeds ``proc/gc_ms{gen=}`` and ``proc/gc_collections{gen=}``). A stall of
the loop is one ``hold`` event and adds to ``serving/holds{kind=}``,
``serving/hold_ms{kind=}`` and ``serving/hold_lost_ms``.

Three pillars, one switch (``profiler.enable()``):

1. **Tracing** (``trace.py``): ``profiler.scope("name")`` /
   ``RecordEvent`` context managers. Inside a jit trace they lower to
   ``jax.named_scope`` (op-name metadata — device time attributable in
   XLA traces); outside they are host ``perf_counter`` spans that double
   as ``jax.profiler.TraceAnnotation``. Export: ``export_chrome_trace``
   (chrome://tracing JSON) and ``scope_summary`` per-scope stats.

2. **Metrics** (``metrics.py``): counters / gauges / histograms in a
   process-global registry — steps, tokens, per-phase ms, collective
   bytes, device-memory high-water marks. ``registry().aggregate()``
   reduces across ranks via distributed/fleet/metrics.py.

3. **Recompilation telemetry** (``recompile.py``): instrumented step
   functions report every jit (re)trace with the triggering abstract
   shapes; the ``profiler/retraces`` counter and ``retraces()`` log make
   silent recompiles in hybrid.py/pipeline.py visible.

Instrumented out of the box: ``HybridPipelineTrainer`` /
``HybridParallelTrainer`` steps (distributed/hybrid.py,
strategy_compiler.py), the pipeline schedule (named phases in the
compiled program), MoE dispatch/combine, ``hapi.Model`` train/eval
batches, and ``hapi.callbacks.ProfilerCallback`` for fit() loops. All
hooks are behind a single enabled check — disabled cost is one bool
read per step.

Async-step-pipeline signals (ISSUE 3; distributed/elastic.py): the
``hybrid/sync_wait`` span times every host←device loss materialization
(under deferred sync it shrinks toward zero — execution already
happened under later dispatches), ``elastic/loss_syncs`` counts them,
``elastic/prefetch_depth`` gauges how many staged batches the input
prefetcher had ready at each consume, and ``ckpt/stall_ms`` /
``ckpt/d2h_bytes`` account the checkpoint snapshot: stall_ms is ONLY
time the training loop was blocked (inline save + wait_snapshot gate),
so sync-vs-streamed saves are directly comparable.

Serving signals (ISSUE 4; paddle_tpu.serving): gauges
``serving/queue_depth``, ``serving/active_slots``,
``serving/page_util`` (allocated fraction of the KV page pool);
counters ``serving/tokens_generated``, ``serving/prefills``,
``serving/ticks``, ``serving/preemptions``,
``serving/requests_finished`` and ``serving/drain_waited`` /
``serving/drain_ready`` (deferred tick outputs the host waited for /
found ready); histograms ``serving/ttft_ms``,
``serving/tick_turnaround_ms`` (dispatch to tokens on the host, one
observation a drained tick) and ``serving/submit_ms`` (host time of
each ``submit()``); host spans ``pt:step/*`` and
``pt:submit/fold_key`` inside any live profiler session; gauges
``serving/mixed_rows`` / ``serving/mixed_rows_decode`` /
``serving/mixed_rows_prefill`` (the prefill-vs-decode row mix of the
last unified tick). Per-shape executable caches (``GPT.generate``'s
jit cache, the Predictor's bucket executables, the paged-engine cache)
report LRU evictions as ``cache_evict/<name>``. The engine's ONE
hot-path program surfaces at the ``serving.tick#N`` recompile site and
must stay at one trace (``ServingEngine.compiled_sites``).

Quick use::

    import paddle_tpu.profiler as profiler
    profiler.enable()
    ... train ...
    print(profiler.summary())          # phases, rates, counters, retraces
    profiler.export_chrome_trace("trace.json")
    profiler.disable()
"""
from __future__ import annotations

from . import device_trace, events, instrument, metrics  # noqa: F401
from . import proc, recompile, sink, ticklog, trace, xla_stats  # noqa: F401
from . import disttrace, live, sketch  # noqa: F401
from .live import AlertRule, LiveAggregator, default_rules  # noqa: F401
from .sketch import QuantileSketch  # noqa: F401
from .disttrace import ClockSync, clock_state  # noqa: F401
from .disttrace import set_clock_state, trace_id  # noqa: F401
from .device_trace import TraceWindow, last_trace_summary  # noqa: F401
from .device_trace import trace_capture  # noqa: F401
from .events import (EventLog, FlightRecorder, dump_flight,  # noqa: F401
                     emit, flight_recorder, latency_breakdown,
                     latency_table, request_latency_stats)
from .events import log as event_log  # noqa: F401
from .instrument import (collective_stats, device_memory_stats,  # noqa: F401
                         estimate_comm_ms, record_collective_stats,
                         record_collectives_from, record_memory_high_water,
                         record_memory_ledger, record_phases,
                         tokens_in_batch)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,  # noqa: F401
                      registry)
from .ticklog import TickLog, tick_logs  # noqa: F401
from .recompile import (mark_trace, retraces, suppressed,  # noqa: F401
                        trace_counts, unique_site, watch)
from .sink import (MetricsSink, active_sink, disable_sink,  # noqa: F401
                   enable_sink, flush_active, prometheus_text)
from .trace import (RecordEvent, annotate, chrome_trace,  # noqa: F401
                    export_chrome_trace, is_enabled, live_spans, phase,
                    scope, scope_summary)
from .xla_stats import program_inventory, record_compiled  # noqa: F401
from .xla_stats import record_lowered  # noqa: F401

__all__ = [
    "enable", "disable", "is_enabled", "reset",
    "scope", "phase", "RecordEvent", "annotate",
    "scope_summary", "chrome_trace", "export_chrome_trace", "live_spans",
    "registry", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "mark_trace", "watch", "retraces", "trace_counts", "suppressed",
    "unique_site",
    "collective_stats", "record_collective_stats",
    "record_collectives_from", "estimate_comm_ms",
    "record_phases", "device_memory_stats", "record_memory_high_water",
    "record_memory_ledger", "tokens_in_batch",
    "summary",
    # per-request event timelines + flight recorder (events.py)
    "emit", "event_log", "EventLog", "latency_breakdown", "latency_table",
    "request_latency_stats", "flight_recorder", "FlightRecorder",
    "dump_flight",
    # persistent metrics sink (sink.py)
    "MetricsSink", "enable_sink", "disable_sink", "active_sink",
    "flush_active", "prometheus_text",
    # compiled-program accounting (xla_stats.py)
    "record_lowered", "record_compiled", "program_inventory",
    # parsed XLA trace windows (device_trace.py)
    "trace_capture", "TraceWindow", "last_trace_summary",
    # cross-host request tracing (disttrace.py, ISSUE 14)
    "trace_id", "clock_state", "set_clock_state", "ClockSync",
    # live mesh telemetry plane (sketch.py / live.py, ISSUE 16)
    "QuantileSketch", "LiveAggregator", "AlertRule", "default_rules",
    # the engine's record of every tick and its holds (ticklog.py, proc.py)
    "TickLog", "tick_logs",
]

# the collector on the record, from the package's import on (proc.py)
proc.install()


def enable(trace_dir=None, reset: bool = True) -> None:
    """Turn profiling on. ``reset`` (default) clears prior host spans,
    the metrics registry, the event log, the program inventory, and the
    public retrace log, so the window's counters and rates cover only
    this session; retrace signature HISTORY is kept (a step function
    first traced before enable must still read as a retrace on its next
    re-trace), and event SEQUENCE NUMBERS are kept (an active sink's
    cursor survives the reset). ``trace_dir`` additionally starts a
    jax/XLA device trace into that directory."""
    if reset:
        # an active sink drains the ring first — a reset must not eat
        # events the sink promised to persist exactly once
        sink.flush_active("reset")
        trace.reset_events()
        metrics.registry().reset()
        recompile.clear_log()
        events.log().clear()
        xla_stats.reset()
        device_trace.reset()
    trace.enable(trace_dir=trace_dir, reset=False)


def disable() -> dict:
    """Stop profiling; returns the full summary()."""
    s = summary()
    trace.disable()
    return s


def reset() -> None:
    """Clear spans, metrics, events, the program inventory, and retrace
    history (enabled flag and event sequence numbers kept; an active
    sink drains the event ring before it empties)."""
    sink.flush_active("reset")
    trace.reset_events()
    metrics.registry().reset()
    recompile.reset()
    events.log().clear()
    xla_stats.reset()
    device_trace.reset()


def summary(aggregate: bool = False) -> dict:
    """One JSON-ready dict with everything this subsystem observed:
    per-scope host spans, metric snapshot (rank-aggregated when
    ``aggregate``), derived rates (tokens/sec, steps/sec over the enabled
    window), per-phase ms gauges, and the retrace log. Also surfaces
    IN-PROCESS what used to be visible only post-mortem in
    metrics.jsonl: ``events_lost`` (lifecycle events aged out of the
    bounded ring — a truncated timeline is a fact about THIS process,
    not just the sink's file) and ``sink`` health (flush count, failed
    flushes, last error)."""
    proc.publish()      # the collector's time, onto its counters
    reg = metrics.registry()
    snap = reg.aggregate() if aggregate else reg.snapshot()
    window_s = trace.enabled_window_s()
    rates = {}
    phases = {}
    for name, s in snap.items():
        if s["type"] == "counter" and window_s > 0 and \
                name.startswith("train/"):
            rates[name.split("/", 1)[1] + "_per_sec"] = round(
                s["value"] / window_s, 3)
        if name.startswith("phase/") and s.get("value") is not None:
            phases[name.split("/", 1)[1]] = round(s["value"], 4)
    return {"enabled_window_s": round(window_s, 6),
            "scopes": trace.scope_summary(),
            "metrics": snap,
            "rates": rates,
            "phases_ms": phases,
            "retraces": recompile.retraces(),
            "programs": xla_stats.inventory(),
            "events_lost": events.log().dropped,
            "sink": sink.stats()}
