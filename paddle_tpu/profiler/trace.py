"""Tracing layer: nested scopes that lower to the right mechanism per
execution regime.

Reference analogue: platform/profiler.h RecordEvent + the chrome-trace
export of profiler.proto. TPU-native translation (SURVEY §5: the host
never sees device op boundaries):

  - **inside a jit trace** a scope is pure metadata — ``jax.named_scope``
    prefixes every op traced under it, so XLA traces / HLO dumps attribute
    device time to the phase. Host timing a tracer would measure tracing,
    not execution, so no host span is recorded there.
  - **outside jit** (eager ops, dispatch, h2d staging, host pre/post) a
    scope ALWAYS opens a ``jax.profiler.TraceAnnotation`` named
    ``pt:<name>`` (keyword ids ride as the annotation's stats), so the
    span sits on the profiler's clock inside any
    ``jax.profiler.start_trace`` session, whoever started it: the session
    is the only switch. While ``enable()`` is on it is also a
    ``perf_counter_ns`` span, nested via a thread-local stack, kept in
    the in-memory event list for ``scope_summary``/``chrome_trace``.

Disabled mode is the fast path: with ``enable()`` off and no profiler
session live (``TraceMe.is_enabled``, the test a TraceMe makes of itself)
``scope()`` hands back one shared no-op context — no allocation, no lock,
no event.

**Set-up phases** (``phase``) are the one exception to the switch: a span
for an edge that happens a few dozen times a process (the package's
import, a trainer's or an engine's constructor, a dispatch site's first
call), recorded whether or not ``enable()`` is on, as one event of kind
``phase`` in the always-on event log. Never on a tick or a step.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional

import jax

from . import events as _lifecycle
from .metrics import registry as _registry

try:  # private jax API with a public-behavior contract (moe.py precedent)
    from jax._src.core import trace_state_clean as _trace_state_clean
except ImportError:  # pragma: no cover - future jax renames
    def _trace_state_clean():
        return True

try:  # is a jax.profiler session live? (TraceMe's own no-op test)
    from jax._src.lib import _profiler as _jaxlib_profiler
    _session_live = _jaxlib_profiler.TraceMe.is_enabled
except (ImportError, AttributeError):  # pragma: no cover - future jaxlib
    def _session_live():
        return True     # open the annotation always: it tests for itself


#: prefix of every host annotation a scope writes into a profiler session
SPAN_PREFIX = "pt:"

# The names ``annotate`` bakes into a program live in its op metadata, and
# jax strips metadata before it hashes a program for the persistent compile
# cache: a program that names its parts would be answered with the nameless
# executable an older checkout left in a shared cache directory, and a
# traced run would attribute nothing (seen on the CPU cache, PR 24). So
# the metadata is part of the key; a checkout compiles once for itself.
try:
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
except AttributeError:  # pragma: no cover - a jax without the option
    pass

_enabled = False
_lock = threading.Lock()
_events: List[tuple] = []      # (full_name, start_ns, end_ns, thread_id)
# A million-step profiled fit must not grow host RAM without bound
# (Histogram's reservoir rule): the chrome-trace span list keeps the
# most recent _MAX_EVENTS, older ones are dropped (counted below) —
# scope_summary stays EXACT via the incremental _agg aggregates.
_MAX_EVENTS = 100_000
_dropped = 0
_agg: Dict[str, list] = {}     # name -> [count, total_ns, min_ns, max_ns]
_t_enable_ns: Optional[int] = None
_t_disable_ns: Optional[int] = None
_jax_trace_dir: Optional[str] = None


_live_stacks: Dict[int, List[str]] = {}   # thread id -> open scope names


def _prune_dead_stacks_locked() -> None:
    """Drop registrations of exited threads (_lock held). threading.local
    frees the per-thread value on thread death but this registry would
    keep a strong reference forever — per-epoch worker threads must not
    grow it without bound."""
    import sys

    alive = set(sys._current_frames())
    for tid in [t for t in _live_stacks if t not in alive]:
        del _live_stacks[tid]


class _TLS(threading.local):
    def __init__(self):
        self.stack: List[str] = []
        # registered so OTHER threads (the resilience step watchdog) can
        # see which scopes are open when a step hangs
        with _lock:
            _prune_dead_stacks_locked()
            _live_stacks[threading.get_ident()] = self.stack


_tls = _TLS()


def live_spans() -> Dict[int, List[str]]:
    """Currently-OPEN host scopes per thread id (the span stack a hung
    step is stuck inside). Only threads with at least one open scope are
    reported; empty when profiling is disabled (scopes no-op)."""
    with _lock:
        _prune_dead_stacks_locked()
        return {tid: list(s) for tid, s in _live_stacks.items() if s}


def is_enabled() -> bool:
    return _enabled


def enable(trace_dir: Optional[str] = None, reset: bool = True) -> None:
    """Turn profiling on. ``trace_dir`` additionally starts a jax/XLA
    device trace (TensorBoard-loadable) into that directory; host scopes
    ride along as TraceAnnotations."""
    global _enabled, _t_enable_ns, _t_disable_ns, _jax_trace_dir
    if reset:
        reset_events()
    _t_enable_ns = time.perf_counter_ns()
    _t_disable_ns = None
    if trace_dir:
        _jax_trace_dir = trace_dir
        jax.profiler.start_trace(trace_dir)
    _enabled = True


def disable() -> Dict[str, dict]:
    """Turn profiling off; returns the per-scope summary (scope_summary)."""
    global _enabled, _t_disable_ns, _jax_trace_dir
    _enabled = False
    _t_disable_ns = time.perf_counter_ns()
    if _jax_trace_dir:
        jax.profiler.stop_trace()
        _jax_trace_dir = None
    return scope_summary()


def reset_events() -> None:
    global _dropped
    with _lock:
        _events.clear()
        _agg.clear()
        _dropped = 0


def enabled_window_s() -> float:
    """Seconds the profiler has been (was) enabled — the denominator for
    rate metrics (tokens/sec, steps/sec)."""
    if _t_enable_ns is None:
        return 0.0
    end = _t_disable_ns if _t_disable_ns is not None \
        else time.perf_counter_ns()
    return max(end - _t_enable_ns, 0) / 1e9


_NO_SPAN = contextlib.nullcontext()


def scope(name: str, **ids):
    """``with profiler.scope("hybrid/fwd"):`` — see module docstring for
    the per-regime lowering. Nesting composes: host spans inherit the
    enclosing scopes' names ("step/h2d") in the in-memory record, traced
    scopes nest via jax.named_scope's own stack. ``scope("step/drain",
    tick=7)``: keyword ids become stats of the ``pt:step/drain``
    annotation (the profiler's own timeline carries the nesting, so the
    annotation keeps the plain name)."""
    if not _enabled and not _session_live():
        return _NO_SPAN
    return _Span(name, **ids)


class _Span:
    """What ``scope`` opens while ``enable()`` is on or a profiler
    session is live."""

    __slots__ = ("name", "_ids", "_t0", "_full", "_jax_ctx", "_mode")

    def __init__(self, name: str, **ids):
        self.name = name
        self._ids = ids
        self._t0 = 0
        self._full = name
        self._jax_ctx = None
        self._mode = 0  # 0: off, 1: recorded host span, 2: named_scope,
        #                 3: annotation only

    def __enter__(self):
        if not _trace_state_clean():
            # inside a jit/grad trace: metadata only, and a host clock
            # would time the tracing
            if _enabled:
                self._mode = 2
                self._jax_ctx = jax.named_scope(self.name)
                self._jax_ctx.__enter__()
            return self
        self._jax_ctx = jax.profiler.TraceAnnotation(
            SPAN_PREFIX + self.name, **self._ids)
        self._jax_ctx.__enter__()
        if not _enabled:
            self._mode = 3
            return self
        self._mode = 1
        stack = _tls.stack
        self._full = "/".join(stack + [self.name]) if stack else self.name
        stack.append(self.name)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._mode == 1:
            t1 = time.perf_counter_ns()
            self._jax_ctx.__exit__(None, None, None)
            if _tls.stack and _tls.stack[-1] == self.name:
                _tls.stack.pop()
            global _dropped
            dt = t1 - self._t0
            with _lock:
                a = _agg.get(self._full)
                if a is None:
                    _agg[self._full] = [1, dt, dt, dt]
                else:
                    a[0] += 1
                    a[1] += dt
                    if dt < a[2]:
                        a[2] = dt
                    if dt > a[3]:
                        a[3] = dt
                _events.append((self._full, self._t0, t1,
                                threading.get_ident()))
                if len(_events) > _MAX_EVENTS:
                    drop = len(_events) - _MAX_EVENTS
                    del _events[:drop]
                    _dropped += drop
        elif self._mode:
            self._jax_ctx.__exit__(None, None, None)
        self._mode = 0
        self._jax_ctx = None
        return False


class RecordEvent(_Span):
    """RAII span under the reference's name (profiler.h:127): explicit
    ``begin()`` / ``end()`` in addition to the context-manager protocol."""

    def begin(self):
        return self.__enter__()

    def end(self):
        self.__exit__(None, None, None)


class _PhaseTLS(threading.local):
    def __init__(self):
        self.stack: List["_Phase"] = []   # this thread's open phases


_phases = _PhaseTLS()
_phase_seq = itertools.count()


class _Phase(RecordEvent):
    """What ``phase`` opens: a span on the event log's clock
    (``perf_counter_ns``, the host's: it says where the host was, so the
    phases of one thread partition its wall time, and device work a phase
    queues is charged to the phase that first waits for it)."""

    __slots__ = ("id", "ids", "parent", "start_ns")

    def __init__(self, name: str, start_ns: Optional[int] = None, **ids):
        # a TraceMe's name ends where its stats begin, at a "#": the
        # annotation says "serving.tick:0" for the site "serving.tick#0"
        super().__init__(name, **{k: str(v).replace("#", ":")
                                  for k, v in ids.items()})
        self.ids = ids
        self.id = next(_phase_seq)
        self.parent: Optional[_Phase] = None
        self.start_ns = start_ns

    def __enter__(self):
        stack = _phases.stack
        self.parent = stack[-1] if stack else None
        stack.append(self)
        if _enabled or _session_live():
            super().__enter__()          # ``pt:<name>``, as every scope
        if self.start_ns is None:
            self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end_ns = time.perf_counter_ns()
        if self._mode:
            super().__exit__(*exc)
        # a child left open (a ``begin()`` whose ``end()`` an exception
        # skipped) goes with its parent
        stack = _phases.stack
        while stack and stack.pop() is not self:
            pass
        _lifecycle.emit(
            "phase", name=self.name, t0_ns=self.start_ns, t1_ns=end_ns,
            id=self.id, parent=self.parent and self.parent.id,
            tid=threading.get_ident(), **self.ids)
        return False


def phase(name: str, start_ns: Optional[int] = None, **ids) -> _Phase:
    """``with profiler.phase("setup/engine", site=...):`` - a set-up
    phase: recorded **whether or not** ``enable()`` is on. At its end it
    emits one event of kind ``phase`` into ``events.log()``: ``name``,
    ``t0_ns`` and ``t1_ns`` on the log's clock, its ``id``, the ``id`` of
    the phase that encloses it on this thread (``parent``, None at the
    top), ``tid`` and the keyword ids. As every scope it opens
    ``pt:<name>`` while a profiler session is live. ``begin()`` /
    ``end()`` as ``RecordEvent``. ``start_ns``: for the one phase that
    began before this module could be imported (the package's import).

    For edges that happen a few dozen times a process. A plain
    ``scope()`` keeps its no-op fast path; nothing on a tick or a step
    may open a phase (tests/test_setup_phases.py counts them)."""
    return _Phase(name, start_ns, **ids)


def import_began(t0_ns: int) -> _Phase:
    """For the first lines of ``paddle_tpu/__init__.py``, which read the
    clock before anything could be imported: sets the gauge
    ``proc/age_at_import_s``, the process's age at ``t0_ns`` (what lay
    before the program), and returns the phase ``setup/import``, begun
    then; the package's last line ends it."""
    _registry().gauge("proc/age_at_import_s").set(
        process_age_s() - (time.perf_counter_ns() - t0_ns) / 1e9)
    return phase("setup/import", start_ns=t0_ns).begin()


def open_phases() -> List[_Phase]:
    """This thread's open phases, outermost first."""
    return list(_phases.stack)


def charge_setup(kind: str, seconds: float, nbytes: Optional[int] = None,
                 **labels) -> None:
    """Set-up work too fine for a phase each (a parameter drawn, a layer
    cast): ``seconds`` more on the counter ``setup/<kind>_s{labels}``, and
    ``nbytes`` more on ``setup/<kind>_bytes{labels}``. The innermost open
    phase, if any, is the last label (``phase=setup/engine/decode_state``),
    so that a reader can take these seconds out of that phase's."""
    if _phases.stack:
        labels["phase"] = _phases.stack[-1].name
    label = "{%s}" % ",".join(f"{k}={v}" for k, v in labels.items()) \
        if labels else ""
    reg = _registry()
    reg.counter(f"setup/{kind}_s{label}").add(seconds)
    if nbytes is not None:
        reg.counter(f"setup/{kind}_bytes{label}").add(nbytes)


def process_age_s() -> float:
    """Seconds since this process was started, from /proc where there is
    one (0.0 where there is none): what lies before the package's first
    line is the interpreter, jax, the device runtime's start and the
    caller's own files."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0.0


def annotate(name: str):
    """Pure device-side annotation: ALWAYS a ``jax.named_scope`` (zero
    runtime cost — op-name metadata only), independent of the enabled
    flag. Use inside jitted step functions so the phase names are baked
    into the compiled program whether or not profiling is on when the
    program is traced."""
    return jax.named_scope(name)


def events() -> List[tuple]:
    with _lock:
        return list(_events)


def scope_summary() -> Dict[str, dict]:
    """Per-scope host-span statistics: {full_name: {count, total_ms,
    mean_ms, min_ms, max_ms}} — from the incremental aggregates, so the
    numbers stay exact even after old spans age out of the bounded
    chrome-trace event list."""
    with _lock:
        items = [(name, list(a)) for name, a in _agg.items()]
    out = {}
    for name, (n, tot, mn, mx) in items:
        out[name] = {"count": n, "total_ms": round(tot / 1e6, 4),
                     "mean_ms": round(tot / n / 1e6, 4),
                     "min_ms": round(mn / 1e6, 4),
                     "max_ms": round(mx / 1e6, 4)}
    return out


def chrome_trace(extra_metadata: Optional[dict] = None) -> dict:
    """Collected host spans as a chrome://tracing / Perfetto-loadable
    object ({"traceEvents": [...]}); counters from the metrics registry
    ride along as metadata so one artifact carries the whole picture."""
    evs = events()
    trace_events = [
        {"name": n, "ph": "X", "ts": t0 / 1e3, "dur": (t1 - t0) / 1e3,
         "pid": 0, "tid": tid, "cat": "host"}
        for n, t0, t1, tid in evs]
    doc = {"traceEvents": trace_events, "displayTimeUnit": "ms"}
    meta = dict(extra_metadata or {})
    if _dropped:
        meta["dropped_events"] = _dropped
    doc["otherData"] = meta
    return doc


def export_chrome_trace(path: str,
                        extra_metadata: Optional[dict] = None) -> str:
    with open(path, "w") as f:
        json.dump(chrome_trace(extra_metadata), f)
    return path
