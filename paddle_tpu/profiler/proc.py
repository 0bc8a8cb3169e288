"""What the host says of the process and of one thread, by its own counters.

A serving loop that stops for 50 ms was stopped by something: the
interpreter's collector, the kernel's scheduler (the thread sat on a run
queue), a page fault, or nothing on this side at all (the device, the
runtime's threads). Each of the first three keeps a counter the program can
read for well under a microsecond, and none was read. This module reads
them; ``ticklog.py`` lays them against the ticks.

- **The collector** (process-wide: a collection holds the interpreter's
  lock, so it stops every thread): one ``gc.callbacks`` entry, installed
  once with the package (``install``), that takes each collection's wall
  time. It runs only when a collection does, changes no threshold and
  freezes nothing. ``gc_ns()`` is the total as one plain integer, for a
  reader that takes a difference every tick; ``publish()`` adds what was
  measured since its last call to the counters ``proc/gc_ms{gen=0|1|2}``
  and ``proc/gc_collections{gen=}``.
- **One thread's own counters** (``ThreadCounters``): CPU time
  (``time.thread_time_ns``), time spent runnable but waiting for a CPU
  (second field of ``/proc/thread-self/schedstat``, the descriptor kept
  open), involuntary context switches and major faults
  (``getrusage(RUSAGE_THREAD)``). Off Linux a field reads ``UNKNOWN``
  (-1), never 0: 0 is a measurement.
- **The machine** (``Pressure``): ``some total`` of ``/proc/pressure/cpu``,
  microseconds in which at least one task of the machine waited for a CPU.
  The one hint at other tenants and at the runtime's own threads that a
  thread's counters cannot give; absent on kernels without PSI.

A collection can begin inside any allocation, also one made while the
registry's lock is held or its dictionary is being walked, so the callback
touches no registry: it adds to plain integers. ``publish()`` is for a place
that holds no lock of the registry's: a tick log when a row that carried a
collection closes, and ``profiler.summary()``.
"""
from __future__ import annotations

import gc
import os
import threading
import time
import weakref
from typing import Optional, Tuple

from .metrics import registry

try:                                    # POSIX
    import resource
    _RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)
except ImportError:                     # pragma: no cover - not POSIX
    resource = None
    _RUSAGE_THREAD = None

__all__ = ["UNKNOWN", "install", "publish", "gc_ns", "ThreadCounters",
           "Pressure"]

#: what a counter reads where the host does not keep it
UNKNOWN = -1

_GENS = (0, 1, 2)
_GC_MS = tuple("proc/gc_ms{gen=%d}" % g for g in _GENS)
_GC_N = tuple("proc/gc_collections{gen=%d}" % g for g in _GENS)

_installed = False
_gc_t0 = 0
_gc_total_ns = 0
#: measured and not yet on the registry's counters, a generation
_unpublished_ns = [0, 0, 0]
_unpublished_n = [0, 0, 0]


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0, _gc_total_ns
    if phase == "start":
        _gc_t0 = time.perf_counter_ns()
        return
    took = time.perf_counter_ns() - _gc_t0
    gen = min(info.get("generation", 2), 2)
    _gc_total_ns += took
    _unpublished_ns[gen] += took
    _unpublished_n[gen] += 1


def install() -> None:
    """Put the collector on the record; once a process."""
    global _installed
    if not _installed:
        _installed = True
        gc.callbacks.append(_on_gc)


def publish() -> None:
    """What the callback measured since the last call, onto the registry's
    counters. Never from inside the registry or the callback itself."""
    reg = registry()
    for gen in _GENS:
        ns, count = _unpublished_ns[gen], _unpublished_n[gen]
        if count:
            _unpublished_ns[gen] -= ns
            _unpublished_n[gen] -= count
            reg.counter(_GC_MS[gen]).add(ns / 1e6)
            reg.counter(_GC_N[gen]).add(count)


def gc_ns() -> int:
    """Wall nanoseconds this process has spent in collections so far."""
    return _gc_total_ns


class _ProcFile:
    """A file of /proc kept open: one ``pread`` a reading; ``None`` where
    there is no such file (not Linux, or a kernel without it)."""

    def __init__(self, path: str):
        try:
            self._fd: Optional[int] = os.open(path, os.O_RDONLY)
        except OSError:
            self._fd = None
        else:
            self._closer = weakref.finalize(self, os.close, self._fd)

    def pread(self, n: int) -> Optional[bytes]:
        if self._fd is None:
            return None
        try:
            return os.pread(self._fd, n, 0)
        except OSError:
            return None

    def close(self) -> None:
        if self._fd is not None:
            self._fd = None
            self._closer()


class ThreadCounters:
    """The calling thread's own counters. ``read()`` is for the thread that
    made the first call; from another thread it opens that thread's file."""

    SCHEDSTAT = "/proc/thread-self/schedstat"

    def __init__(self):
        self._file: Optional[_ProcFile] = None
        self._tid: Optional[int] = None

    def read(self) -> Tuple[int, int, int, int]:
        """``(cpu_ns, runq_wait_ns, involuntary switches, major faults)``,
        each a running total of this thread; ``UNKNOWN`` where the host
        keeps none."""
        if self._tid != threading.get_ident():
            self.close()
            self._tid = threading.get_ident()
            self._file = _ProcFile(self.SCHEDSTAT)
        try:
            runq = int(self._file.pread(96).split()[1])
        except (AttributeError, IndexError, ValueError):
            runq = UNKNOWN              # no file, or not its format
        if _RUSAGE_THREAD is None:
            nivcsw = majflt = UNKNOWN
        else:
            ru = resource.getrusage(_RUSAGE_THREAD)
            nivcsw, majflt = ru.ru_nivcsw, ru.ru_majflt
        return time.thread_time_ns(), runq, nivcsw, majflt

    def close(self) -> None:
        if self._file is not None:
            self._file.close()


class Pressure:
    """``some total`` of ``/proc/pressure/cpu``: microseconds so far in
    which some task of the machine waited for a CPU."""

    PATH = "/proc/pressure/cpu"

    def __init__(self):
        self._file = _ProcFile(self.PATH)

    def read(self) -> Optional[int]:
        """The total in microseconds; ``None`` where the kernel keeps
        none."""
        try:
            first = self._file.pread(256).split(b"\n", 1)[0]
            return int(first.rsplit(b"total=", 1)[1])
        except (AttributeError, IndexError, ValueError):
            return None

    def close(self) -> None:
        self._file.close()
