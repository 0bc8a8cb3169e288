"""Device-time truth: parsed XLA trace windows (ISSUE 11).

Everything device-side the profiler reported before this module was an
attribution or a model: ``phase/comm_measured_ms`` is step wall time
apportioned by cost-analysis bytes (truthful about magnitude, silent
about overlap), per-op "timings" were named-scope metadata. This module
is the measurement: wrap a window of hot-loop iterations in
``jax.profiler.trace`` and parse the trace-event JSON the profiler
exports (``plugins/profile/<run>/*.trace.json.gz``) with **stdlib
only** — gzip + json, no tensorboard/tensorflow dependency. From the
parsed timeline it derives, per capture window:

- **device-busy wall time** (interval union of device-op slices) and
  the host-gap split: ``wall = device_busy + host_gap`` — the measured
  version of PR 3's dispatch-vs-execution gap;
- a **per-op-category breakdown** (matmul / attention / scatter-gather
  / elementwise / collective) by slice count and microseconds;
- **per-collective measured durations by kind** (all_reduce /
  all_gather / reduce_scatter / ppermute / all_to_all), joined against
  the per-site collective BYTE accounting xla_stats already keeps — so
  bytes and microseconds finally sit in one record;
- a measured **compute∩comm overlap fraction**: |union(collective
  slices) ∩ union(non-collective device slices)| / |union(collective
  slices)| — in [0, 1], 0 when nothing overlapped (or no collectives
  ran), 1 when every collective microsecond had compute in flight.
  This upgrades ``phase/comm_measured_ms`` (apportioned) with
  ``phase/comm_traced_ms`` (measured; the old gauge is kept for
  comparison);
- a **goodput/MFU ledger**: cost-analysis model FLOPs (xla_stats) ×
  traced executions ÷ measured wall time vs the device's peak, plus
  ``goodput_busy_frac`` (device-busy share of wall — the fraction of
  the window the device was doing anything at all).

**Site correlation.** Trace slices carry ``args.hlo_module``
(``jit_step``, ``jit_tick``, ...). ``xla_stats.record_lowered`` /
``record_compiled`` register each recorded program's HLO module name
next to its dispatch-site name (``hybrid.step#0``,
``serving.tick#1``), so parsed slices join the program inventory —
and its FLOPs/bytes/collective-bytes — on the site key the rest of the
profiler already uses. Record programs (``record_program_stats()`` /
``profile_step_phases``) BEFORE capturing, or modules land in
``unattributed_modules``. Two live programs lowered from same-named
functions share a module name; such rows are flagged ``ambiguous``.

**Per-site executions** are estimated from the trace itself: the
minimum per-op-name slice count inside a module (ops inside compiled
loops repeat per iteration; top-level ops run exactly once per
execution, so the minimum is the execution count). The capture's
``steps`` hint (iterations the caller wrapped) rides alongside.

**CPU semantics (honest).** On the CPU backend the "device" slices are
XLA:CPU **thunks** executed on host threads (``args.hlo_op`` on the
thunk-executor thread) — real measured per-op wall time of the
compiled program, but host-scheduled: overlap is ~0 by construction
and the busy union measures the thunk executor, not an accelerator.
On TPU the same parser reads the device-stream slices. Every parser
path is exercised by checked-in fixture tests on any backend.

**Peak FLOPs** for MFU: an accelerator gets its published bf16 peak
from ``profiler/peaks.py`` (a ``device_kind`` the table does not list
is an error); CPU gets a one-shot MEASURED matmul calibration at the
first capture (source ``"calibrated"`` — ISSUE 16 satellite, retiring
the nominal placeholder), falling back to the labeled nominal
``_NOMINAL_CPU_FLOPS`` only if the measurement itself fails —
``peak_flops_source`` says which one was used; pass ``peak_flops=``
or set ``PADDLE_PEAK_FLOPS`` to override (the env var always wins).

Entry points::

    with device_trace.capture(steps=4, label="hybrid.step") as cap:
        for _ in range(4): step()
    cap.summary                      # the parsed window

    win = device_trace.TraceWindow(length=2, every=100, start=10)
    for i in range(n_steps):
        with win.step():
            trainer.step(batch)      # steps 10-11, 110-111, ... traced
    win.last                         # newest summary

Wired through: ``profile_step_phases(trace_window=k)`` (hybrid +
strategy_compiler), ``ServingEngine.trace_window()``, ``serve_bench
--trace-window N`` / ``bench.py`` profiler blocks. Each summary is
folded into registry gauges (``phase/comm_traced_ms``,
``phase/comm_overlap_frac``, ``trace/*``), persisted by an active sink
as ``trace_summary.json`` (schema-checked in CI), and attached to
flight-recorder dumps.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

from .metrics import registry

__all__ = [
    "TraceParseError", "capture", "trace_capture", "TraceWindow",
    "find_trace_file", "load_trace_events", "parse_timeline",
    "summarize", "record_summary", "last_summary",
    "last_trace_summary", "categorize_op", "collective_kind",
    "overlap_fraction", "interval_union_ms", "default_peak_flops",
]


class TraceParseError(ValueError):
    """A trace file that cannot be read as trace-event JSON: truncated
    gzip, malformed JSON, or a document without ``traceEvents``."""


# ---------------------------------------------------------------------------
# op classification
# ---------------------------------------------------------------------------
#: substring -> collective kind, checked in order (reduce_scatter before
#: all_reduce is irrelevant — the spellings are disjoint; both the HLO
#: dash form and the StableHLO underscore form are matched, and async
#: -start/-done slices classify to the same kind)
_COLLECTIVE_KINDS = (
    ("all-reduce", "all_reduce"), ("all_reduce", "all_reduce"),
    ("reduce-scatter", "reduce_scatter"),
    ("reduce_scatter", "reduce_scatter"),
    ("all-gather", "all_gather"), ("all_gather", "all_gather"),
    ("all-to-all", "all_to_all"), ("all_to_all", "all_to_all"),
    ("collective-permute", "ppermute"),
    ("collective_permute", "ppermute"), ("ppermute", "ppermute"),
    ("collective-broadcast", "collective_broadcast"),
    ("collective_broadcast", "collective_broadcast"),
)

_MATMUL_PAT = ("dot", "conv", "einsum", "matmul", "cublas", "gemm")
_ATTENTION_PAT = ("attention", "attn", "softmax", "flash")
_SCATTER_GATHER_PAT = ("scatter", "gather", "dynamic-slice",
                       "dynamic_slice", "dynamic-update-slice",
                       "dynamic_update_slice", "sort", "take")

#: the four compute categories + collectives; sums over a summary's
#: ``categories`` cover every parsed device slice exactly once
CATEGORIES = ("matmul", "attention", "scatter-gather", "elementwise",
              "collective")


def collective_kind(name: str) -> Optional[str]:
    """Collective kind of an op/slice name, or None. Understands the
    compiled-HLO dash spelling (``all-reduce-start``), the StableHLO
    underscore spelling, and fusion names that embed either."""
    n = name.lower()
    for pat, kind in _COLLECTIVE_KINDS:
        if pat in n:
            return kind
    return None


def categorize_op(name: str) -> str:
    """Category of one device-op slice by its (HLO) name. On TPU the
    op name carries jax named-scope prefixes (``fwd/attn/dot.3``) so
    scope words like "attention" classify; on CPU the thunk name is
    the bare HLO instruction (``dot.4``, ``broadcast_maximum_fusion``)
    and classification rides the opcode embedded in it."""
    n = name.lower()
    if collective_kind(n) is not None:
        return "collective"
    if any(p in n for p in _ATTENTION_PAT):
        return "attention"
    if any(p in n for p in _MATMUL_PAT):
        return "matmul"
    if any(p in n for p in _SCATTER_GATHER_PAT):
        return "scatter-gather"
    return "elementwise"


# ---------------------------------------------------------------------------
# interval arithmetic (the overlap/busy math, unit-tested directly)
# ---------------------------------------------------------------------------
def _merge(intervals: List[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    if not intervals:
        return []
    ivs = sorted(intervals)
    out = [list(ivs[0])]
    for s, e in ivs[1:]:
        if s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def interval_union_ms(intervals: List[Tuple[float, float]]) -> float:
    """Total covered length of ``[(start_us, end_us), ...]`` in ms."""
    return sum(e - s for s, e in _merge(intervals)) / 1e3


def _intersection_len_us(a: List[Tuple[float, float]],
                         b: List[Tuple[float, float]]) -> float:
    """|union(a) ∩ union(b)| in us (both merged by the caller)."""
    total = 0.0
    i = j = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def overlap_fraction(comm: List[Tuple[float, float]],
                     compute: List[Tuple[float, float]]) -> float:
    """Fraction of collective time with compute in flight: |union(comm)
    ∩ union(compute)| / |union(comm)|, clamped to [0, 1]; 0.0 when no
    collective slices exist (nothing to overlap)."""
    cm = _merge(comm)
    denom = sum(e - s for s, e in cm)
    if denom <= 0:
        return 0.0
    frac = _intersection_len_us(cm, _merge(compute)) / denom
    return min(max(frac, 0.0), 1.0)


# ---------------------------------------------------------------------------
# trace-file loading (stdlib only)
# ---------------------------------------------------------------------------
def find_trace_file(log_dir: str) -> Optional[str]:
    """Newest ``*.trace.json.gz`` under ``log_dir/plugins/profile/*/``
    (the jax profiler's TensorBoard export layout); falls back to a
    ``perfetto_trace.json.gz`` (same document minus metadata) or a bare
    ``*.trace.json(.gz)`` directly under ``log_dir``."""
    pats = (os.path.join(log_dir, "plugins", "profile", "*",
                         "*.trace.json.gz"),
            os.path.join(log_dir, "plugins", "profile", "*",
                         "perfetto_trace.json.gz"),
            os.path.join(log_dir, "*.trace.json.gz"),
            os.path.join(log_dir, "*.trace.json"))
    for pat in pats:
        files = [f for f in glob.glob(pat)
                 if not os.path.basename(f).startswith("perfetto")
                 or "perfetto" in pat]
        if files:
            return max(files, key=os.path.getmtime)
    return None


def load_trace_events(path: str) -> dict:
    """Read one trace-event document ({"traceEvents": [...]} or a bare
    event list) from ``path`` (gzipped by extension). Raises
    :class:`TraceParseError` on truncated gzip / malformed JSON /
    wrong document shape — the negative paths fixture tests pin."""
    try:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt", encoding="utf-8",
                    errors="replace") as f:
            doc = json.load(f)
    except (OSError, EOFError, ValueError, UnicodeDecodeError) as e:
        # gzip truncation surfaces as EOFError, bad gzip magic as
        # OSError(BadGzipFile), malformed JSON as JSONDecodeError
        raise TraceParseError(f"{path}: {type(e).__name__}: {e}") from e
    if isinstance(doc, list):
        doc = {"traceEvents": doc}
    if not isinstance(doc, dict) or \
            not isinstance(doc.get("traceEvents"), list):
        raise TraceParseError(
            f"{path}: not a trace-event document (no traceEvents list)")
    return doc


# ---------------------------------------------------------------------------
# timeline parsing
# ---------------------------------------------------------------------------
class Timeline:
    """Parsed slices of one capture window.

    ``device_ops``: [(name, module|None, ts_us, dur_us)] — slices with
    HLO metadata (``args.hlo_op``/``hlo_module``) or sitting under a
    ``/device:*`` process (TPU streams). ``host_spans``: named host
    annotation slices (TraceAnnotations — profiler scopes — and step
    markers), runtime-internal noise filtered out. The window bounds
    (``t_min_us``/``t_max_us``) cover device ops + host annotations
    ONLY — jax's own trace-session setup/teardown slices (seconds on a
    first capture) must not count as hot-loop host gap.
    """

    __slots__ = ("device_ops", "host_spans", "events_total",
                 "t_min_us", "t_max_us")

    def __init__(self):
        self.device_ops: List[Tuple[str, Optional[str], float, float]] = []
        self.host_spans: List[Tuple[str, float, float]] = []
        self.events_total = 0
        self.t_min_us: Optional[float] = None
        self.t_max_us: Optional[float] = None


_HOST_NOISE = ("PjitFunction", "ParseArguments", "ThreadpoolListener",
               "ThunkExecutor")


def _is_host_annotation(name: str) -> bool:
    # keep profiler scopes ("hybrid/fwd", "serving/tick") and step
    # annotations; drop the python tracer ("$file:line fn") and C++
    # runtime internals ("TfrtCpuExecutable::Execute")
    if name.startswith("$") or "::" in name:
        return False
    if any(p in name for p in _HOST_NOISE):
        return False
    return "/" in name or name.startswith("train ")


def parse_timeline(doc: dict) -> Timeline:
    """Split a trace-event document into device-op slices and host
    annotation spans. Events without a duration (metadata, counters,
    instant events) only extend the window bounds."""
    tl = Timeline()
    device_pids = set()
    evs = doc.get("traceEvents", [])
    tl.events_total = len(evs)
    for e in evs:
        if not isinstance(e, dict):
            continue
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pname = (e.get("args") or {}).get("name", "")
            if isinstance(pname, str) and "/device:" in pname:
                device_pids.add(e.get("pid"))
    for e in evs:
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        try:
            ts = float(e["ts"])
            dur = float(e.get("dur", 0.0))
        except (KeyError, TypeError, ValueError):
            continue
        name = e.get("name")
        if not isinstance(name, str):
            continue
        args = e.get("args") or {}
        is_device = (isinstance(args, dict)
                     and ("hlo_op" in args or "hlo_module" in args)) \
            or e.get("pid") in device_pids
        if is_device:
            module = args.get("hlo_module") if isinstance(args, dict) \
                else None
            tl.device_ops.append((name, module, ts, dur))
        elif _is_host_annotation(name):
            tl.host_spans.append((name, ts, dur))
        else:
            continue
        if tl.t_min_us is None or ts < tl.t_min_us:
            tl.t_min_us = ts
        if tl.t_max_us is None or ts + dur > tl.t_max_us:
            tl.t_max_us = ts + dur
    return tl


# ---------------------------------------------------------------------------
# peak FLOPs (MFU denominator)
# ---------------------------------------------------------------------------
#: CPU FALLBACK for hosts where the measured matmul calibration below
#: fails (CPU tests only — accelerator peaks live in profiler/peaks.py
#: and an unknown accelerator is an error); peak_flops_source labels
#: which one a ledger actually used.
_NOMINAL_CPU_FLOPS = 5e10

#: one-shot CPU calibration cache: (peak FLOP/s or None, done flag) —
#: measured at the FIRST capture's summarize and reused for the
#: process's lifetime (a per-capture re-measure would make MFUs from
#: the same run mutually incomparable)
_cpu_calibration: Optional[float] = None
_cpu_calibrated = False
_calib_lock = threading.Lock()


def _measure_cpu_peak_flops(n: int = 512,
                            reps: int = 5) -> Optional[float]:
    """Measured f32 matmul throughput of THIS host (best of ``reps``
    timed ``n x n`` BLAS multiplies after one warmup) — the honest CPU
    MFU denominator the old nominal placeholder stood in for. Best-of
    (not mean) deliberately: the denominator should be the machine's
    demonstrated peak, so reported MFU stays <= 1 instead of drifting
    above it when a timing rep got descheduled. Returns None on any
    failure — the caller falls back to the labeled nominal value,
    never guesses."""
    try:
        import numpy as np

        rng = np.random.default_rng(0)
        a = rng.standard_normal((n, n)).astype(np.float32)
        b = rng.standard_normal((n, n)).astype(np.float32)
        a @ b                               # warm the BLAS path
        best = None
        flop = 2.0 * n ** 3
        for _ in range(reps):
            t0 = time.perf_counter()
            (a @ b).sum()                   # .sum() defeats lazy eval
            dt = time.perf_counter() - t0
            if dt > 0 and (best is None or dt < best):
                best = dt
        return None if best is None else flop / best
    except Exception:  # pragma: no cover - exotic BLAS failure
        return None


def default_peak_flops() -> Tuple[Optional[float], str]:
    """(peak FLOP/s, source label) for the local device. Precedence:
    ``PADDLE_PEAK_FLOPS`` env var; on an accelerator the published
    table (``profiler/peaks.py`` — a ``device_kind`` it does not list
    raises); on the CPU backend the one-shot measured matmul
    calibration (source ``"calibrated"``), then the labeled nominal
    CPU fallback."""
    global _cpu_calibration, _cpu_calibrated
    env = os.environ.get("PADDLE_PEAK_FLOPS")
    if env:
        try:
            return float(env), "env:PADDLE_PEAK_FLOPS"
        except ValueError:
            pass
    import jax

    from .peaks import device_peak

    dev = jax.devices()[0]
    if dev.platform != "cpu":
        return device_peak(dev).bf16_flops, f"published:{dev.device_kind}"
    with _calib_lock:
        if not _cpu_calibrated:
            _cpu_calibration = _measure_cpu_peak_flops()
            _cpu_calibrated = True
        if _cpu_calibration is not None:
            return _cpu_calibration, "calibrated"
    return _NOMINAL_CPU_FLOPS, "nominal-cpu-placeholder"


# ---------------------------------------------------------------------------
# summarization
# ---------------------------------------------------------------------------
def _cat_table() -> Dict[str, dict]:
    return {c: {"count": 0, "ms": 0.0} for c in CATEGORIES}


def summarize(doc_or_timeline, steps: Optional[int] = None,
              peak_flops: Optional[float] = None,
              label: str = "trace") -> dict:
    """Derive the full device-time summary (module docstring) from a
    parsed timeline (or raw trace-event document). Pure host math —
    never dispatches device work, so it is safe on post-mortem paths.

    ``steps``: how many hot-loop iterations the capture wrapped (the
    per-step normalizations; None leaves them out). ``peak_flops``:
    MFU denominator override (default :func:`default_peak_flops`).
    """
    from . import xla_stats as _xla

    tl = doc_or_timeline if isinstance(doc_or_timeline, Timeline) \
        else parse_timeline(doc_or_timeline)
    if peak_flops is None:
        peak_flops, peak_src = default_peak_flops()
    else:
        peak_src = "caller"

    try:
        import jax

        platform = jax.devices()[0].platform
    except Exception:
        platform = "unknown"

    wall_ms = 0.0
    if tl.t_min_us is not None and tl.t_max_us is not None:
        wall_ms = (tl.t_max_us - tl.t_min_us) / 1e3

    categories = _cat_table()
    collectives: Dict[str, dict] = {}
    comm_iv: List[Tuple[float, float]] = []
    compute_iv: List[Tuple[float, float]] = []
    all_iv: List[Tuple[float, float]] = []
    # per-module aggregation: slices, per-op-name counts, categories
    mod_ops: Dict[Optional[str], dict] = {}
    for name, module, ts, dur in tl.device_ops:
        iv = (ts, ts + dur)
        all_iv.append(iv)
        cat = categorize_op(name)
        categories[cat]["count"] += 1
        categories[cat]["ms"] += dur / 1e3
        if cat == "collective":
            kind = collective_kind(name)
            c = collectives.setdefault(kind, {"count": 0, "ms": 0.0})
            c["count"] += 1
            c["ms"] += dur / 1e3
            comm_iv.append(iv)
        else:
            compute_iv.append(iv)
        m = mod_ops.setdefault(module, {
            "ops": 0, "device_ms": 0.0, "op_counts": {},
            "categories": _cat_table(), "collectives": {}})
        m["ops"] += 1
        m["device_ms"] += dur / 1e3
        m["op_counts"][name] = m["op_counts"].get(name, 0) + 1
        m["categories"][cat]["count"] += 1
        m["categories"][cat]["ms"] += dur / 1e3
        if cat == "collective":
            mc = m["collectives"].setdefault(
                collective_kind(name), {"count": 0, "ms": 0.0})
            mc["count"] += 1
            mc["ms"] += dur / 1e3

    device_busy_ms = interval_union_ms(all_iv)
    host_gap_ms = max(wall_ms - device_busy_ms, 0.0)
    busy_frac = device_busy_ms / wall_ms if wall_ms > 0 else 0.0
    comm_ms = sum(c["ms"] for c in collectives.values())
    comm_overlap = overlap_fraction(comm_iv, compute_iv)

    # --- site correlation + per-site ledger -------------------------------
    module_sites = _xla.module_sites()
    ambiguous = _xla.ambiguous_modules()
    inv = {s.site: s for s in map(_xla.get, _xla.inventory())
           if s is not None}
    sites: Dict[str, dict] = {}
    unattributed: Dict[str, dict] = {}
    for module, m in mod_ops.items():
        # min per-op-name count estimates executions (loop-body ops
        # repeat per iteration; unconditional top-level ops run exactly
        # once per execution) — a LOWER bound for programs with
        # lax.cond branches, whose branch-local ops skip executions
        execs = min(m["op_counts"].values()) if m["op_counts"] else 0
        site = module_sites.get(module) if module else None
        row = {
            "module": module,
            "ops": m["ops"],
            "device_ms": round(m["device_ms"], 4),
            "executions": execs,
            "executions_source": "trace_min_op_count",
            "categories": {c: {"count": v["count"],
                               "ms": round(v["ms"], 4)}
                           for c, v in m["categories"].items()
                           if v["count"]},
            "collectives": {k: {"count": v["count"],
                                "ms": round(v["ms"], 4)}
                            for k, v in m["collectives"].items()},
        }
        if site is None:
            unattributed[module or "<unknown>"] = {
                "ops": row["ops"], "device_ms": row["device_ms"],
                "executions": execs}
            continue
        if module in ambiguous:
            row["ambiguous"] = True
        sites[site] = row

    # with ONE attributed site and a steps hint, the hint is the exact
    # execution count (the caller counted its own iterations/ticks) —
    # branch-skipping can't fool it
    if steps and len(sites) == 1:
        row = next(iter(sites.values()))
        row["executions"] = int(steps)
        row["executions_source"] = "steps_hint"

    model_flops_total = 0.0
    flops_known = False
    for site, row in sites.items():
        execs = row["executions"]
        row["device_ms_per_exec"] = round(
            row["device_ms"] / execs, 4) if execs else None
        ps = inv.get(site)
        if ps is not None and ps.flops is not None and execs:
            # MODEL flops (cost analysis counts every op statically —
            # both lax.cond branches included) × traced executions: a
            # join of modeled cost onto measured time, stated as such
            row["flops_per_exec"] = ps.flops
            flops = ps.flops * execs
            model_flops_total += flops
            flops_known = True
            if row["device_ms"] > 0:
                row["model_flops_per_s"] = round(
                    flops / (row["device_ms"] / 1e3), 3)
                if peak_flops:
                    row["mfu"] = round(
                        flops / (row["device_ms"] / 1e3) / peak_flops,
                        6)
        # join: modeled collective BYTES (per execution, from the
        # program's compiled HLO) next to the traced microseconds
        if ps is not None and ps.collectives:
            for kind, cb in ps.collectives.items():
                dst = row["collectives"].setdefault(
                    kind, {"count": 0, "ms": 0.0})
                dst["bytes_per_exec"] = cb.get("bytes")
                dst["modeled_ops_per_exec"] = cb.get("ops")

    # fold per-kind modeled bytes up to the window level
    for row in sites.values():
        execs = row["executions"]
        for kind, c in row["collectives"].items():
            if kind in collectives and "bytes_per_exec" in c \
                    and c["bytes_per_exec"] is not None:
                collectives[kind]["bytes"] = (
                    collectives[kind].get("bytes", 0)
                    + c["bytes_per_exec"] * max(execs, 1))
    for c in collectives.values():
        c["ms"] = round(c["ms"], 4)

    wall_s = wall_ms / 1e3 if wall_ms > 0 else None
    ledger = {
        "peak_flops": peak_flops,
        "peak_flops_source": peak_src,
        "model_flops_total": model_flops_total if flops_known else None,
        "model_flops_per_s": round(model_flops_total / wall_s, 3)
        if flops_known and wall_s else None,
        "mfu": round(model_flops_total / wall_s / peak_flops, 6)
        if flops_known and wall_s and peak_flops else None,
        "goodput_busy_frac": round(busy_frac, 6),
        "steps": steps,
        "wall_ms_per_step": round(wall_ms / steps, 4)
        if steps else None,
        "device_busy_ms_per_step": round(device_busy_ms / steps, 4)
        if steps else None,
        "host_gap_ms_per_step": round(host_gap_ms / steps, 4)
        if steps else None,
    }

    host: Dict[str, dict] = {}
    for name, _ts, dur in tl.host_spans:
        h = host.setdefault(name, {"count": 0, "ms": 0.0})
        h["count"] += 1
        h["ms"] += dur / 1e3
    for h in host.values():
        h["ms"] = round(h["ms"], 4)

    return {
        "kind": "device_trace_summary",
        "label": label,
        "platform": platform,
        "unix_time": round(time.time(), 3),
        "steps": steps,
        "events_total": tl.events_total,
        "device_ops": len(tl.device_ops),
        "empty": not tl.device_ops,
        "wall_ms": round(wall_ms, 4),
        "device_busy_ms": round(device_busy_ms, 4),
        "host_gap_ms": round(host_gap_ms, 4),
        "busy_frac": round(busy_frac, 6),
        "categories": {c: {"count": v["count"], "ms": round(v["ms"], 4)}
                       for c, v in categories.items()},
        "collectives": collectives,
        "comm_ms": round(comm_ms, 4),
        "comm_overlap_frac": round(comm_overlap, 6),
        "comm_traced_ms_per_step": round(comm_ms / steps, 4)
        if steps else None,
        "sites": sites,
        "unattributed_modules": unattributed,
        "ledger": ledger,
        "host_annotations": host,
    }


# ---------------------------------------------------------------------------
# summary recording: gauges + sink artifact + last-summary slot
# ---------------------------------------------------------------------------
_last_lock = threading.Lock()
_last: Optional[dict] = None


def last_summary() -> Optional[dict]:
    """The most recent recorded trace summary (what the flight
    recorder attaches to watchdog/rollback dumps); None before any
    capture completed."""
    with _last_lock:
        return _last


def reset() -> None:
    global _last
    with _last_lock:
        _last = None


def record_summary(summary: dict) -> dict:
    """Fold a summary into the registry gauges, persist it through an
    active sink as ``trace_summary.json`` (atomic rewrite, prom-file
    latest-wins contract), and remember it for flight dumps. Never
    raises — capture teardown must not take the hot loop down.

    Degraded summaries (a skipped capture, a parse error — no
    ``wall_ms``) are NOT recorded: they stay visible on the capture
    object, but must not clobber the last good summary, feed the
    gauges, or overwrite the sink artifact with a document that
    violates its own schema. They are counted instead
    (``trace/windows_degraded``)."""
    global _last
    if "wall_ms" not in summary:
        try:
            registry().counter("trace/windows_degraded").add(1)
        except Exception:
            pass
        return summary
    try:
        reg = registry()
        reg.gauge("trace/device_busy_ms").set(summary["device_busy_ms"])
        reg.gauge("trace/host_gap_ms").set(summary["host_gap_ms"])
        reg.gauge("trace/goodput_busy_frac").set(summary["busy_frac"])
        reg.gauge("trace/device_ops").set(float(summary["device_ops"]))
        # measured comm: coexists with the apportioned
        # phase/comm_measured_ms and the modeled phase/comm_ms
        per_step = summary.get("comm_traced_ms_per_step")
        reg.gauge("phase/comm_traced_ms").set(
            per_step if per_step is not None else summary["comm_ms"])
        reg.gauge("phase/comm_overlap_frac").set(
            summary["comm_overlap_frac"])
        for kind, c in summary.get("collectives", {}).items():
            reg.gauge(f"trace/comm/{kind}_ms").set(c["ms"])
        led = summary.get("ledger") or {}
        if led.get("mfu") is not None:
            reg.gauge("trace/mfu").set(led["mfu"])
        if led.get("model_flops_per_s") is not None:
            reg.gauge("trace/model_flops_per_s").set(
                led["model_flops_per_s"])
        reg.counter("trace/windows_recorded").add(1)
    except Exception:
        pass
    with _last_lock:
        _last = summary
    try:
        from . import sink as _sink

        s = _sink.active_sink()
        if s is not None:
            path = os.path.join(s.directory, "trace_summary.json")
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(summary, f)
            os.replace(tmp, path)
    except Exception:
        pass
    return summary


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------
class capture:  # noqa: N801 - context manager, lowercase like scope
    """Wrap a window of hot-loop iterations in a jax profiler trace and
    parse it on exit::

        with device_trace.capture(steps=4, label="hybrid.step") as cap:
            for _ in range(4):
                step()           # materialize each step's output!
        cap.summary              # dict (see summarize)

    The caller must SYNC the wrapped work (fetch a result leaf) before
    the block ends — device work still in flight when the trace stops
    is cut off, exactly like any profiler window.

    ``log_dir=None`` captures into a temp dir deleted after parsing
    (``keep_files=True`` keeps it; ``cap.trace_file`` points at the
    parsed artifact). ``steps`` may be (re)assigned inside the block —
    engine wrappers set it to the measured tick count before exit.
    Only one jax trace can run per process: if another is active, the
    capture degrades to a no-op with ``summary = {"skipped": ...}``
    rather than raising into the hot loop. Parse failures land in
    ``summary["error"]``; degraded summaries stay on the capture
    object but are NOT folded into gauges / the sink artifact / the
    flight slot (:func:`record_summary` counts them as
    ``trace/windows_degraded`` instead).
    """

    def __init__(self, log_dir: Optional[str] = None,
                 steps: Optional[int] = None,
                 peak_flops: Optional[float] = None,
                 label: str = "trace", keep_files: bool = False):
        self.log_dir = log_dir
        self.steps = steps
        self.peak_flops = peak_flops
        self.label = label
        self.keep_files = keep_files or log_dir is not None
        self.summary: Optional[dict] = None
        self.trace_file: Optional[str] = None
        self._dir: Optional[str] = None
        self._tmp = False
        self._started = False

    def __enter__(self) -> "capture":
        import jax

        if self.log_dir is None:
            self._dir = tempfile.mkdtemp(prefix="ptpu-trace-")
            self._tmp = True
        else:
            os.makedirs(self.log_dir, exist_ok=True)
            self._dir = self.log_dir
        try:
            # the .trace.json.gz is part of the standard export — no
            # create_perfetto_trace re-encode needed (find_trace_file
            # reads either spelling)
            jax.profiler.start_trace(self._dir)
            self._started = True
        except Exception as e:
            # another trace active (profiler.enable(trace_dir=...) or a
            # nested window): degrade, don't break the hot loop
            self.summary = {"kind": "device_trace_summary",
                            "label": self.label, "skipped": str(e),
                            "empty": True}
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._started:
            import jax

            try:
                jax.profiler.stop_trace()
            except Exception as e:
                self.summary = {"kind": "device_trace_summary",
                                "label": self.label,
                                "error": f"stop_trace: {e}",
                                "empty": True}
            else:
                if exc_type is None:
                    self._parse()
        if self.summary is not None and exc_type is None:
            record_summary(self.summary)
        if self._tmp and not self.keep_files and self._dir:
            shutil.rmtree(self._dir, ignore_errors=True)
            self.trace_file = None
        return False

    def _parse(self) -> None:
        path = find_trace_file(self._dir)
        if path is None:
            self.summary = {"kind": "device_trace_summary",
                            "label": self.label,
                            "error": "no trace file exported",
                            "empty": True}
            return
        self.trace_file = path
        try:
            doc = load_trace_events(path)
            self.summary = summarize(doc, steps=self.steps,
                                     peak_flops=self.peak_flops,
                                     label=self.label)
        except TraceParseError as e:
            self.summary = {"kind": "device_trace_summary",
                            "label": self.label, "error": str(e),
                            "empty": True}


#: package-level spellings (``profiler.trace_capture`` /
#: ``profiler.last_trace_summary``) — module-local names stay short
trace_capture = capture
last_trace_summary = last_summary


class TraceWindow:
    """Windowed capture scheduler: trace iterations N..N+length-1,
    every ``every`` iterations (``every=0``: one window only)::

        win = TraceWindow(length=2, every=100, start=10)
        for i in range(steps):
            with win.step():
                trainer.step(batch)
        win.last            # newest summary; win.summaries holds all

    ``max_windows`` bounds how many windows fire (0 = unbounded); each
    window is one :class:`capture` (steps=length), so summaries carry
    the per-step normalizations. Window starts that collide with an
    already-running jax trace are skipped and counted
    (``win.skipped``)."""

    def __init__(self, length: int = 2, every: int = 0, start: int = 0,
                 log_dir: Optional[str] = None,
                 peak_flops: Optional[float] = None,
                 label: str = "window", max_windows: int = 0,
                 keep_files: bool = False):
        if length < 1:
            raise ValueError("length must be >= 1")
        if every and every < length:
            raise ValueError("every must be 0 or >= length "
                             "(windows must not overlap)")
        self.length = int(length)
        self.every = int(every)
        self.start = int(start)
        self.log_dir = log_dir
        self.peak_flops = peak_flops
        self.label = label
        self.max_windows = int(max_windows)
        self.keep_files = keep_files
        self.summaries: List[dict] = []
        self.skipped = 0
        self._i = 0
        self._cap: Optional[capture] = None
        self._end = -1

    @property
    def last(self) -> Optional[dict]:
        return self.summaries[-1] if self.summaries else None

    def _should_start(self, i: int) -> bool:
        if self.max_windows and len(self.summaries) >= self.max_windows:
            return False
        if i < self.start:
            return False
        if self.every:
            return (i - self.start) % self.every == 0
        return i == self.start

    def step(self) -> "_WindowStep":
        """Context manager wrapping ONE hot-loop iteration."""
        return _WindowStep(self)


class _WindowStep:
    __slots__ = ("_w",)

    def __init__(self, window: TraceWindow):
        self._w = window

    def __enter__(self):
        w = self._w
        if w._cap is None and w._should_start(w._i):
            n = len(w.summaries)
            sub = os.path.join(w.log_dir, f"window-{n}") \
                if w.log_dir else None
            cap = capture(log_dir=sub, steps=w.length,
                          peak_flops=w.peak_flops,
                          label=f"{w.label}#{n}",
                          keep_files=w.keep_files)
            cap.__enter__()
            if cap.summary is not None and "skipped" in cap.summary:
                # another jax trace is live — don't fight it; release
                # the temp dir __enter__ already made (nothing was
                # captured into it, and __exit__ will never run)
                cap._started = False
                if cap._tmp and cap._dir:
                    shutil.rmtree(cap._dir, ignore_errors=True)
                w.skipped += 1
            else:
                w._cap = cap
                w._end = w._i + w.length - 1
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        w = self._w
        try:
            if w._cap is not None and (w._i >= w._end
                                       or exc_type is not None):
                cap = w._cap
                w._cap = None
                cap.__exit__(exc_type, exc, tb)
                if exc_type is None and cap.summary is not None:
                    w.summaries.append(cap.summary)
        finally:
            w._i += 1
        return False
