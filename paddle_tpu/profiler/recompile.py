"""Recompilation telemetry: make silent jit retraces visible.

A hybrid/pipeline step function is supposed to trace ONCE and then hit
the jit cache forever; every extra trace is minutes of XLA compile time
silently folded into a training run (a changed batch shape, a dtype
drift, a python-scalar argument). The reference framework never had this
failure mode (programs were built ahead of time); a jit-staged framework
needs a watcher.

Mechanism: instrumented step functions call ``mark_trace(site, *trees)``
at the TOP of their traced body. Python side effects run exactly once per
trace, so the call itself is the cache-miss signal — zero per-step cost,
no jax internals. The watcher keeps each site's abstract signature
(shape/dtype of every leaf); any trace after a site's first is a
**retrace** and is recorded with the shapes that triggered it, diffed
against the previous signature.

What a compilation COST is jax's to say: one ``jax.monitoring`` listener,
registered here at import, hears the seconds each program took to trace,
to lower and to compile or to fetch from the persistent cache. They are
charged to the dispatch site whose ``setup/first_call`` phase
(``trace.phase``) is open on the compiling thread, else to the site
``eager`` (the small programs of eager operations), in three places: the
site's record in the inventory (``xla_stats.charge_compile``), the
process's counters ``compile/*`` and one event of kind ``compile`` in the
always-on event log, so that a reader can say what the counters stood at
when a window opened. The listener runs at compilations only.
"""
from __future__ import annotations

import itertools
import logging
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List

import jax

from . import events as _events
from . import trace as _trace
from .metrics import registry

logger = logging.getLogger("paddle_tpu.profiler")

_lock = threading.Lock()
_sites: Dict[str, List[tuple]] = {}       # site -> signature history
_retraces: List[dict] = []
_MAX_HISTORY = 64
_suppress = 0
_site_seq = itertools.count()


def unique_site(prefix: str) -> str:
    """A process-unique site name for per-instance step functions (two
    trainers must not alias one site — the second's FIRST trace would
    read as the first's retrace)."""
    return f"{prefix}#{next(_site_seq)}"


@contextmanager
def suppressed():
    """Traces inside this context update signature history but not the
    public retrace counter/log — for internal diagnostic lowerings
    (aot_lower for collective accounting, memory_analysis) that re-trace
    by design and are not silent recompiles."""
    global _suppress
    _suppress += 1
    try:
        yield
    finally:
        _suppress -= 1


def _aval_sig(x: Any) -> tuple:
    aval = getattr(x, "aval", None)
    if aval is not None:
        return (tuple(aval.shape), str(aval.dtype))
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    return ((), type(x).__name__)


def signature(*trees) -> tuple:
    return tuple(_aval_sig(leaf)
                 for t in trees for leaf in jax.tree_util.tree_leaves(t))


def mark_trace(site: str, *trees) -> None:
    """Record that ``site`` is being traced with these arguments. Call
    from INSIDE the traced function body (first line). Signature history
    is tracked unconditionally (a site first traced while profiling was
    off must still detect its first retrace after enable); the public
    counter/log only move while the profiler is enabled."""
    sig = signature(*trees)
    with _lock:
        hist = _sites.setdefault(site, [])
        is_retrace = bool(hist)
        prev = hist[-1] if hist else None
        hist.append(sig)
        if len(hist) > _MAX_HISTORY:
            del hist[: len(hist) - _MAX_HISTORY]
    if is_retrace and _trace.is_enabled() and not _suppress:
        # zip_longest: a leaf-count change (argument added/removed) must
        # show up as a diff entry, not be truncated to "same signature"
        ev = {"site": site, "trace_no": len(hist),
              "prev_signature": prev, "signature": sig,
              "changed": [
                  {"index": i, "prev": p, "new": n}
                  for i, (p, n) in enumerate(
                      itertools.zip_longest(prev, sig)) if p != n]}
        with _lock:
            _retraces.append(ev)
        registry().counter("profiler/retraces").add(1)
        logger.warning(
            "jit retrace at %s (trace #%d): %s", site, len(hist),
            ev["changed"] if ev["changed"]
            else "same signature (function object rebuilt)")


def watch(fn, site: str = None):  # noqa: RUF013 - mirrors functools style
    """Wrap an arbitrary function so every (re)trace of it is recorded:
    ``step = jax.jit(profiler.watch(step_fn, "my.step"))``."""
    name = site or getattr(fn, "__qualname__", getattr(fn, "__name__",
                                                       "fn"))

    def wrapped(*args, **kwargs):
        mark_trace(name, args, kwargs)
        return fn(*args, **kwargs)

    wrapped.__name__ = getattr(fn, "__name__", "wrapped")
    return wrapped


def retraces() -> List[dict]:
    with _lock:
        return list(_retraces)


def clear_log() -> None:
    """Clear the public retrace log but KEEP signature history — a site
    first traced before this call must still read as a retrace on its
    next re-trace (enable() calls this; reset() drops history too)."""
    with _lock:
        _retraces.clear()


def trace_counts() -> Dict[str, int]:
    with _lock:
        return {site: len(h) for site, h in _sites.items()}


def reset() -> None:
    with _lock:
        _sites.clear()
        _retraces.clear()


# ---------------------------------------------------------------------------
# what each compilation cost, by site
# ---------------------------------------------------------------------------
#: the phase a dispatch site opens around its first call, with ``site=``
FIRST_CALL = "setup/first_call"
#: the site of every compilation outside such a phase
EAGER = "eager"

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: around the whole of ``compile_or_get_cached``: the last event of a
#: program, built by the backend or fetched
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_FETCH_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class _Pending(threading.local):
    """What this thread's next program has cost so far."""

    def __init__(self):
        self.traces: List[tuple] = []    # (start, end), outermost only
        self.lower_s = 0.0
        self.fetched = False


_pending = _Pending()


def _on_compile_event(event: str, duration: float, **_) -> None:
    p = _pending
    if event == _TRACE_EVENT:
        # a jitted function traced inside another reports too, before
        # the one that holds it: only the outermost is time of its own
        end = time.perf_counter()
        start = end - duration
        p.traces = [t for t in p.traces if t[0] < start] + [(start, end)]
    elif event == _LOWER_EVENT:
        p.lower_s += duration
    elif event == _FETCH_EVENT:
        p.fetched = True
    elif event == _BACKEND_EVENT:
        cost = {"trace_s": sum(e - s for s, e in p.traces),
                "lower_s": p.lower_s,
                "backend_s": 0.0 if p.fetched else duration,
                # key, read and deserialization: what the hit cost
                "cache_fetch_s": duration if p.fetched else 0.0,
                "cache_hit": p.fetched}
        p.traces, p.lower_s, p.fetched = [], 0.0, False
        site = next((ph.ids.get("site", EAGER)
                     for ph in reversed(_trace.open_phases())
                     if ph.name == FIRST_CALL), EAGER)
        reg = registry()
        for k in ("trace_s", "lower_s", "backend_s", "cache_fetch_s"):
            reg.counter("compile/" + k).add(cost[k])
        reg.counter("compile/cache_hits" if cost["cache_hit"]
                    else "compile/cache_misses").add(1)
        reg.counter("compile/programs").add(1)
        _events.emit("compile", site=site, **cost)
        from . import xla_stats    # it imports this module

        xla_stats.charge_compile(site, **cost)


jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
