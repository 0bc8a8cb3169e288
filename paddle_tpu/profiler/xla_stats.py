"""Compiled-program accounting: a per-process program inventory.

The recompile telemetry (recompile.py) says WHEN a dispatch site
traced; this module says WHAT each site's compiled program costs:
compile wall-time, and XLA's own ``compiled.cost_analysis()`` FLOPs /
bytes-accessed. The inventory is keyed by the same site names the
recompile watcher uses (``serving.tick#0``, ``hybrid.step#1``, ...),
so "which program", "how often traced" and "what it costs" join on one
key. This is the harness ROADMAP items 2/3 need: a kernel or
quantization experiment's before/after is attributable per compiled
program, not inferred from whole-run wall clock.

Callers hold a ``jax.stages.Lowered`` (``jitted.lower(*avals)`` —
ShapeDtypeStructs are enough, nothing materializes):

    stats = xla_stats.record_lowered("serving.tick#0", lowered)

``record_lowered`` times the ``compile()`` call (honest wall-time of
THIS compilation — on a warm XLA process-level cache it measures the
cache hit, which is the cost the caller actually paid) and folds the
cost analysis into the registry as ``xla/<site>/compile_ms`` /
``.../flops`` / ``.../bytes_accessed`` gauges plus the inventory.

What the PROCESS paid for a site's program is measured where it was paid:
a site that opens a ``setup/first_call`` phase around its first call gets
that call's seconds of tracing, lowering, backend compilation and cache
retrieval, and whether the cache held it (``charge_compile``, fed by
recompile.py's ``jax.monitoring`` listener; gauges ``xla/<site>/trace_s``
... ``cache_hit``). Its ``compile_ms`` is then that call's backend
compilation or cache fetch, and a later ``record_lowered`` adds the cost
analysis and leaves the timing alone. Compilations outside any first call
are charged to the bucket ``eager``: ``get("eager")``, not a dispatch
site and not in ``inventory()``.

CPU caveat (documented, not hidden): the CPU backend's cost analysis
reports ``flops``/``bytes accessed`` from the optimized HLO but no
per-op timing model; on some backends/versions ``cost_analysis()``
raises — recorded as ``cost_available: False`` with compile time
still kept. Accounting never raises into the caller's hot path.
"""
from __future__ import annotations

import re
import threading
import time
from typing import Dict, Optional, Set

from . import recompile as _recompile
from .metrics import registry

__all__ = ["ProgramStats", "record_lowered", "record_compiled",
           "charge_compile",
           "normalize_cost", "inventory", "program_inventory", "get",
           "reset", "category_breakdown", "module_sites",
           "ambiguous_modules", "register_module_site"]


class ProgramStats:
    """One dispatch site's compiled-program record.

    Beyond the cost-analysis totals it carries (when the compiled HLO
    text was analyzable): the HLO **module name** (the join key parsed
    device traces report as ``args.hlo_module`` — device_trace.py
    correlates slices back to sites through it), a **per-op-category
    FLOPs/bytes breakdown** (``categories``: matmul / attention /
    scatter-gather / elementwise / collective, derived from the
    optimized HLO's entry computation — the same categories traced
    time is bucketed into, so modeled cost and measured microseconds
    join on one axis), and **per-collective-kind byte counts**
    (``collectives``: result-buffer bytes per execution by kind, the
    instrument.collective_stats convention)."""

    __slots__ = ("site", "compile_ms", "flops", "bytes_accessed",
                 "cost", "recorded_unix", "module", "categories",
                 "collectives", "flops_unattributed", "programs",
                 "trace_s", "lower_s", "backend_s", "cache_fetch_s",
                 "cache_hit")

    def __init__(self, site: str, compile_ms: Optional[float],
                 flops: Optional[float], bytes_accessed: Optional[float],
                 cost: dict, module: Optional[str] = None,
                 categories: Optional[dict] = None,
                 collectives: Optional[dict] = None,
                 flops_unattributed: Optional[float] = None):
        self.site = site
        self.compile_ms = compile_ms
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.cost = cost
        self.module = module
        self.categories = categories or {}
        self.collectives = collectives or {}
        self.flops_unattributed = flops_unattributed
        self.recorded_unix = time.time()
        #: compilations the process made for this site (``charge_compile``)
        #: and their seconds; ``cache_hit``: the cache held every one
        self.programs = 0
        self.trace_s = self.lower_s = 0.0
        self.backend_s = self.cache_fetch_s = 0.0
        self.cache_hit: Optional[bool] = None

    def to_dict(self) -> dict:
        return {
            "site": self.site,
            "compile_ms": None if self.compile_ms is None
            else round(self.compile_ms, 3),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "cost_available": bool(self.cost),
            "module": self.module,
            "categories": self.categories,
            "collectives": self.collectives,
            "flops_unattributed": self.flops_unattributed,
            "programs": self.programs,
            "trace_s": self.trace_s, "lower_s": self.lower_s,
            "backend_s": self.backend_s,
            "cache_fetch_s": self.cache_fetch_s,
            "cache_hit": self.cache_hit,
        }


_lock = threading.Lock()
_programs: Dict[str, ProgramStats] = {}
#: HLO module name -> dispatch site (the trace-slice join key); a
#: module name claimed by TWO different sites (two jits of same-named
#: functions) lands in _ambiguous — correlation stays possible but is
#: flagged.
_module_sites: Dict[str, str] = {}
_ambiguous: Set[str] = set()

_HLO_MODULE_RE = re.compile(r"^HloModule ([^,\s]+)", re.M)
_MLIR_MODULE_RE = re.compile(r"^module @([^\s(]+)", re.M)


def register_module_site(module: str, site: str) -> None:
    """Register (or re-register) the HLO-module-name -> site mapping
    device_trace uses to attribute parsed slices."""
    with _lock:
        prior = _module_sites.get(module)
        if prior is not None and prior != site:
            _ambiguous.add(module)
        _module_sites[module] = site


def module_sites() -> Dict[str, str]:
    with _lock:
        return dict(_module_sites)


def ambiguous_modules() -> Set[str]:
    with _lock:
        return set(_ambiguous)


# ---------------------------------------------------------------------------
# per-op-category breakdown of one compiled program's HLO text
# ---------------------------------------------------------------------------
# one scheduled instruction: `%name = type op(...)` — type is either a
# single `f32[64,48]{1,0}` or a tuple `(f32[..], s32[..])`
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\(?[a-z][a-z0-9]+\[[^=]*?)\s"
    r"([a-z][a-z0-9\-]*)\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]+)\[([0-9,]*)\]")
_DOT_LHS_RE = re.compile(r"\(([a-z][a-z0-9]+)\[([0-9,]*)\]")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=([a-z0-9?]+)_([a-z0-9?]+)->")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SKIP_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id"})


def _dims(dim_str: str) -> list:
    return [int(d) for d in dim_str.split(",") if d]


def _result_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in _dims(dims):
            n *= d
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def _dot_flops(line: str, result_type: str,
               types: Optional[dict] = None) -> Optional[float]:
    """2 * prod(result dims) * prod(lhs contracting dims) — exact for
    dot_general including batch dims (both live in the result).
    ``types`` {instruction: result type}: jax 0.9 prints operands bare
    (``dot(%x, %w)``), so the lhs shape is looked up by its name."""
    args = line[line.index("dot("):]
    lhs = _DOT_LHS_RE.search(args)
    bare = re.match(r"dot\(%([\w.\-]+)", args)
    if lhs is None and bare and types:
        lhs = _DOT_LHS_RE.search("(" + types.get(bare.group(1), ""))
    cm = _CONTRACT_RE.search(line)
    rm = _SHAPE_RE.search(result_type)
    if not (lhs and cm and rm):
        return None
    lhs_dims = _dims(lhs.group(2))
    contract = 1
    for i in _dims(cm.group(1)):
        if i >= len(lhs_dims):
            return None
        contract *= lhs_dims[i]
    result = 1
    for d in _dims(rm.group(2)):
        result *= d
    return 2.0 * result * contract


def _conv_flops(line: str, result_type: str) -> Optional[float]:
    """2 * prod(result) * (kernel elements / output channels): each
    output point multiplies the whole kernel volume for its channel.
    Output-channel position parsed from dim_labels' rhs spec ('o')."""
    idx = line.find("convolution(")
    if idx < 0:
        return None
    operands = _DOT_LHS_RE.findall(line[idx:])
    dl = _DIM_LABELS_RE.search(line)
    rm = _SHAPE_RE.search(result_type)
    if len(operands) < 2 or not dl or not rm:
        return None
    rhs_dims = _dims(operands[1][1])
    rhs_spec = dl.group(2)
    if "o" not in rhs_spec or len(rhs_spec) != len(rhs_dims):
        return None
    out_ch = rhs_dims[rhs_spec.index("o")]
    kernel = 1
    for d in rhs_dims:
        kernel *= d
    result = 1
    for d in _dims(rm.group(2)):
        result *= d
    return 2.0 * result * (kernel / max(out_ch, 1))


def category_breakdown(hlo_text: str,
                       total_flops: Optional[float] = None) -> dict:
    """Per-op-category FLOPs/bytes breakdown of ONE compiled program's
    optimized-HLO text — the modeled counterpart of device_trace's
    per-category measured time, on the same category axis.

    Bytes are result-buffer sizes of the ENTRY computation's scheduled
    instructions (each is one thunk/slice in a device trace — counting
    fusion bodies too would double-count); per the collective_stats
    convention these are per-execution buffer bytes, not wire bytes.
    FLOPs are computed analytically for every ``dot`` / ``convolution``
    in ANY computation (fusions can swallow them) and attributed to
    matmul; the remainder against ``total_flops`` (cost_analysis's own
    number, when given) is returned as ``flops_unattributed`` so the
    totals still reconcile. Categories: matmul / attention /
    scatter-gather / elementwise / collective.

    Returns ``{"categories": {cat: {ops, bytes[, flops]}},
    "flops_unattributed": float | None}`` — the reconciliation number
    sits NEXT TO the homogeneous per-category table, never inside it.
    """
    from .device_trace import categorize_op

    cats: Dict[str, dict] = {}
    in_entry = False
    matmul_flops = 0.0
    flops_known = False
    types: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
            continue
        if line.startswith("}"):
            in_entry = False
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rtype, op = m.groups()
        types[name] = rtype
        if op in _SKIP_OPS:
            continue
        if op == "dot" or op == "convolution":
            f = _dot_flops(line, rtype, types) if op == "dot" \
                else _conv_flops(line, rtype)
            if f is not None:
                matmul_flops += f
                flops_known = True
        if not in_entry:
            continue
        cat = categorize_op(f"{name} {op}")
        c = cats.setdefault(cat, {"ops": 0, "bytes": 0})
        c["ops"] += 1
        c["bytes"] += _result_bytes(rtype)
    if flops_known:
        cats.setdefault("matmul", {"ops": 0, "bytes": 0})
        cats["matmul"]["flops"] = matmul_flops
    return {"categories": dict(sorted(cats.items())),
            "flops_unattributed":
            max(total_flops - matmul_flops, 0.0)
            if total_flops is not None and flops_known else None}


def normalize_cost(ca) -> dict:
    """``cost_analysis()`` returns a list of per-device dicts on some
    jax versions, a dict on others, None on backends without it — one
    plain dict out (empty when unavailable)."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return dict(ca) if ca else {}


def record_compiled(site: str, compiled,
                    compile_s: Optional[float] = None) -> ProgramStats:
    """Fold an already-compiled program's cost analysis (and, when the
    caller timed it, the compile wall-time) into the inventory +
    registry. Also analyzes the compiled HLO text (best-effort): the
    module name registers the trace-slice join key
    (``register_module_site``), and the per-op-category +
    per-collective breakdowns ride on the ProgramStats. Text analysis
    is skipped silently where ``as_text()`` is unavailable — the
    totals still land."""
    try:
        cost = normalize_cost(compiled.cost_analysis())
    except Exception:
        cost = {}
    flops = cost.get("flops")
    byts = cost.get("bytes accessed")
    module = categories = collectives = unattrib = None
    try:
        text = compiled.as_text()
        m = _HLO_MODULE_RE.search(text) or _MLIR_MODULE_RE.search(text)
        if m:
            module = m.group(1)
            register_module_site(module, site)
        bd = category_breakdown(
            text, None if flops is None else float(flops))
        categories = bd["categories"]
        unattrib = bd["flops_unattributed"]
        from .instrument import collective_stats

        cs = collective_stats(text)
        collectives = {op: {"ops": n, "bytes": cs["bytes"].get(op, 0)}
                       for op, n in cs["ops"].items()}
    except Exception:
        pass
    stats = ProgramStats(site, None if compile_s is None
                         else compile_s * 1e3,
                         None if flops is None else float(flops),
                         None if byts is None else float(byts), cost,
                         module=module, categories=categories,
                         collectives=collectives,
                         flops_unattributed=unattrib)
    with _lock:
        prior = _programs.get(site)
        if prior is not None and prior.programs:
            # the process's own compilation of this site was measured:
            # that is what it paid, not this diagnostic compile
            for k in ("compile_ms", "programs", "trace_s", "lower_s",
                      "backend_s", "cache_fetch_s", "cache_hit"):
                setattr(stats, k, getattr(prior, k))
        _programs[site] = stats
    reg = registry()
    if stats.compile_ms is not None:
        reg.gauge(f"xla/{site}/compile_ms").set(stats.compile_ms)
    if stats.flops is not None:
        reg.gauge(f"xla/{site}/flops").set(stats.flops)
    if stats.bytes_accessed is not None:
        reg.gauge(f"xla/{site}/bytes_accessed").set(stats.bytes_accessed)
    reg.counter("xla/programs_recorded").add(1)
    return stats


def record_lowered(site: str, lowered) -> ProgramStats:
    """Compile ``lowered`` (timed — the recorded compile wall-time)
    and record its cost analysis. The compile runs suppressed: it is a
    diagnostic lowering by design, not a silent recompile."""
    with _recompile.suppressed():
        t0 = time.perf_counter()
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
    return record_compiled(site, compiled, compile_s=dt)


def charge_compile(site: str, trace_s: float, lower_s: float,
                   backend_s: float, cache_fetch_s: float,
                   cache_hit: bool) -> None:
    """One compilation the process made for ``site`` (recompile.py's
    listener calls this): its seconds are added to the site's record,
    made here if this is the first the inventory hears of the site, and
    the gauges ``xla/<site>/...`` follow."""
    with _lock:
        st = _programs.get(site)
        if st is None:
            st = _programs[site] = ProgramStats(site, 0.0, None, None, {})
        elif not st.programs:
            st.compile_ms = 0.0          # a diagnostic timing gives way
        st.programs += 1
        st.trace_s += trace_s
        st.lower_s += lower_s
        st.backend_s += backend_s
        st.cache_fetch_s += cache_fetch_s
        st.cache_hit = cache_hit and st.cache_hit is not False
        st.compile_ms += (backend_s + cache_fetch_s) * 1e3
        vals = {k: getattr(st, k) for k in
                ("compile_ms", "trace_s", "lower_s", "backend_s",
                 "cache_fetch_s", "cache_hit")}
    reg = registry()
    for k, v in vals.items():
        reg.gauge(f"xla/{site}/{k}").set(v)


def get(site: str) -> Optional[ProgramStats]:
    with _lock:
        return _programs.get(site)


def inventory() -> Dict[str, dict]:
    """JSON-ready {site: stats} — what bench blocks and the sink
    embed. Dispatch sites only: the ``eager`` bucket is ``get("eager")``."""
    with _lock:
        return {site: s.to_dict() for site, s in sorted(_programs.items())
                if site != _recompile.EAGER}


#: package-level spelling (``profiler.program_inventory()``) — the
#: module-local name stays the short one
program_inventory = inventory


def reset() -> None:
    """Clear the inventory AND the module->site join maps: a stale
    mapping would attribute trace slices to a site the (cleared)
    inventory no longer holds, and a prior engine generation's
    registration would permanently flag a re-used module name
    ambiguous. The contract stays: record programs (again) before
    capturing a trace window."""
    with _lock:
        _programs.clear()
        _module_sites.clear()
        _ambiguous.clear()
