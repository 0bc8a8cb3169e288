"""What every family needs around the system under test: the process's
age, host spans on the profiler's clock, the count of compilations, the
profiler window and the device's own report.
"""
from __future__ import annotations

import contextlib
import os
import shutil
import time
from typing import Dict, List, Optional, Tuple

from . import loader, tracered

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def process_age_s() -> float:
    """Seconds since this process was started, from /proc where there is
    one: set-up begins when the interpreter does, not at the first line
    of the benchmark."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED_AT


_IMPORTED_AT = time.perf_counter()


def enable_compile_cache() -> str:
    """jax's persistent compilation cache at a fixed place inside the
    checkout, or where JAX_COMPILATION_CACHE_DIR says. The program's own
    ``enable_compile_cache`` picks the same ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = loader.root_file(".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_tpu(chips: int):
    """The cell's chips, or SystemExit: there is no CPU mode."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"perfbench: jax found no TPU (devices: {devs}); "
                         "a cell is measured on the chip or not at all")
    if len(devs) < chips:
        raise SystemExit(f"perfbench: the cell needs {chips} chips, jax "
                         f"found {len(devs)}")
    return devs[:chips]


class Context:
    """One run of one cell, as the family sees it."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 devices: list):
        self.cell = cell["cell"]
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.spans: List[Tuple[str, float, float]] = []
        self.compiles: List[float] = []      # host times of compilations
        self.setup_s: Optional[float] = None
        self.t_open: Optional[float] = None
        self.trace_doc: Optional[dict] = None
        self._trace_dir = loader.root_file(".perfbench_trace")
        self._tracing = self._traced = False
        self._site_counts: Dict[str, int] = {}
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    # seeds: any whole number up to a little over 2**31
    @property
    def seed31(self) -> int:
        return self.seed % (2 ** 31 - 1)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.compiles.append(time.perf_counter())

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: kept on the host clock, and written into the
        profiler's trace so that idle gaps can be laid against it."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracered.SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def open_window(self) -> float:
        """Set-up ends here. Returns the host time of the opening."""
        from paddle_tpu.profiler import recompile

        self.setup_s = process_age_s()
        self._site_counts = dict(recompile.trace_counts())
        self.t_open = time.perf_counter()
        return self.t_open

    def compiles_in(self, t0: float, t1: float) -> int:
        """Programs built or fetched from the cache between two host
        times, and retraces of the program's own marked sites since the
        window opened."""
        from paddle_tpu.profiler import recompile

        now = recompile.trace_counts()
        retraced = sum(max(0, n - self._site_counts.get(site, 0))
                       for site, n in now.items())
        return sum(t0 <= t <= t1 for t in self.compiles) + retraced

    # --- the profiler window ---------------------------------------------
    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self._trace_dir, ignore_errors=True)   # the last one
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # every Python call is a cost
        options.host_tracer_level = 2       # ...the benchmark's spans stay
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)
        self._tracing = self._traced = True

    def stop_trace(self) -> None:
        """Stops the profiler. The trace is read later, by ``read_trace``:
        reading takes seconds that belong to no tick and no step."""
        import jax

        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False

    def read_trace(self) -> None:
        """The traced run's file stays in ``.perfbench_trace`` until the
        next traced run, for ``describe_trace.py`` and a look by hand."""
        self.stop_trace()
        path = tracered.find_xplane(self._trace_dir) if self._traced \
            else None
        if path is not None:
            self.trace_doc = tracered.read_xplane(path)


def device_report(devices, trace_doc: Optional[dict]) -> dict:
    """The ``device`` object of the last line: the device as jax reports
    it, and the memory peak of the fullest chip the cell used."""
    import jax

    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": jax.device_count(), "memory_peak_bytes": max(peaks)}
    if trace_doc is not None:
        win = tracered.window_of(trace_doc)
        if win is not None:
            out["busy_s"] = tracered.busy_s(trace_doc)
            out["window_s"] = (win[1] - win[0]) / 1e9
    return out


def breakdown(trace_doc: dict) -> dict:
    gaps = sorted(tracered.idle_gaps_by_span(trace_doc).items(),
                  key=lambda kv: -kv[1])[:10]
    return {"device_ops": tracered.top_ops(trace_doc, 10),
            "idle_gaps": [[k, v] for k, v in gaps]}
