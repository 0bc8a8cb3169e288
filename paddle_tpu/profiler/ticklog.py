"""The serving engine's record of every tick, and the holds it names.

``trace.scope`` is a no-op while no profiler session is live, so in the
stretch a deployment (or a benchmark's judged window) actually runs in, the
``pt:step/*`` spans do not exist. This is the record that is always there:
one ring of ``CAPACITY`` rows an engine, preallocated ``int64``, one row a
``ServingEngine.step()``, written from a scratch list that is reused (no
array is made on a tick). ``profiler.tick_logs()`` finds the newest logs by
engine id, ``ServingEngine.tick_log()`` is the same object. The module keeps
the ``KEPT`` newest logs itself: a benchmark's reader is handed the run and
not the engine, and comes when the engine is garbage already (a log holds
no reference to its engine, so keeping one keeps 4 MB and nothing else).

**A row** is the interval from one ``step()``'s entry to the next one's, and
the tick that step dispatched. It holds (``COLUMNS``):

- the tick: ``tick`` (its number, the ``tick=`` of the ``pt:step/*`` spans;
  -1: the step dispatched nothing), ``rows`` (decode rows), ``chunk_tokens``,
  ``starved`` (1: the dispatch found the device with nothing left to run,
  the previous tick's output was ready; 0: work was still queued; -1: not
  asked, the speculative path), and, written when the tick is drained,
  ``arrive`` (the moment its tokens reached the host) and ``waited`` (1: the
  host got there first and was blocked for them, so ``arrive`` is the
  device's own completion to within the hand-off; 0: they lay ready);
- ``perf_counter_ns`` at the boundaries the ``pt:step/*`` spans mark:
  ``t_step`` (entry), ``t_admit``, ``t_chunks``, ``t_grow``, ``t_build``,
  ``t_dispatch`` (each read as its span closes), ``t_return``, ``t_end``
  (the next entry). One read serves as a part's end and the next one's
  beginning, and the drain's three reads (before the span, as
  ``np.asarray`` returns, after the span) feed ``arrive``, the requests'
  first-token time, ``serving/tick_turnaround_ms`` and
  ``serving/drain_waited|drain_ready`` as well: the engine reads no other
  clock on a tick;
- the **parts** of the interval, nanoseconds each (``PARTS``): ``admit``,
  ``chunks``, ``grow``, ``build``, ``dispatch`` (the spans of those names;
  the speculative engine's inline sync lands in ``dispatch``), ``tail`` (the
  step's bookkeeping after the dispatch: the in-flight entry, chunk events,
  prefix insertion, gauges), ``drain_wait`` (blocked in ``np.asarray`` for a
  tick the device had not finished), ``drain_host`` (the token loop, and the
  copy of a tick that lay ready), ``outside`` (from ``step()``'s return to
  the next entry: the caller's loop). They sum to ``t_end - t_step`` less
  ``idle``: time after a ``step()`` that left the engine with no live slot
  and no queue is its caller's sleep, not a part. A step of such an engine
  that neither dispatched nor drained keeps no row at all;
- the **engine thread's own counters** over the interval (``proc.py``):
  ``cpu_ns``, ``runq_ns`` (runnable, waiting for a CPU), ``nivcsw``,
  ``majflt``, the collector's ``gc_ns``, and ``proc_cpu_ns``, the CPU time
  of all the process's threads (``time.process_time_ns``): a hold in which
  the thread got no CPU and the process did was a wait for another thread
  (the interpreter's lock, the runtime); one in which neither did stopped
  the whole process. -1: the host keeps none.

**A hold** is named when a row closes, against running medians over the
last ``WINDOW`` rows of its shape class (a tick with a prefill chunk and one
without keep separate baselines: a legitimately heavier tick is no hold),
refreshed every ``REFRESH`` rows and silent until a class has ``REFRESH``:

- **host hold**: the row's host parts together (all but ``drain_wait``)
  exceed their baseline by at least ``max(10 ms, baseline)``. ``where`` is
  the part with the largest excess over its own baseline.
- **device hold**: the wait for a tick exceeds the usual wait for a tick of
  its class by as much, with no host hold in the row, **and the tick came
  that late on the device's own schedule** (its arrival, less the last
  waited-for arrival, the cadence of the ticks between and the engine's idle
  time): a host that ran ahead through ticks nobody drains (prefill chunks
  alone) waits long for the next one and has lost nothing. The thread was
  blocked, so from inside it cannot tell the chip from the runtime's threads
  or the transfer: ``where="device_wait"``.

``lost_ms`` is one definition for both, taken on the device's side: the
arrival of the first waited-for tick after the hold ended, minus the arrival
of the last waited-for tick before it began, minus the cadence baseline (median
arrival-to-arrival interval of consecutive waited-for ticks, a shape class)
of every tick between them, minus the engine's idle time between them; never
below 0. A host hold that the queued ticks hid reads 0, a starved one reads
what the device idled, a device hold reads its excess. So a hold's event is
emitted when that next tick has arrived and the dispatch after the hold has
said whether it was ``starved`` (``None`` for a hold inside ``dispatch``
itself: it fell between that dispatch's look at the device and the next
one's): a row or two after its own. Where no such
arrival comes (the engine ran out of work) ``lost_ms`` is ``None``. Two
holds between the same two arrivals: the first is charged, the second reads
0.

Each hold is one ``hold`` event in the always-on log (``events.py``) and adds
to ``serving/holds{kind=}``, ``serving/hold_ms{kind=}`` and
``serving/hold_lost_ms``. **Explained** is what ``gc_ms + runq_ms`` cover of
the excess; the rest is ``unexplained_ms``, and a device hold is unexplained
whole. Fault *time* cannot be had without delay accounting: ``majflt`` is a
count.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import events as _events
from . import proc as _proc
from .metrics import registry as _registry

__all__ = ["TickLog", "tick_logs", "COLUMNS", "PARTS", "CAPACITY",
           "HOLD_NS", "KEPT"]

#: rows an engine keeps: a 45 s window of the fastest cell (8,500 ticks)
CAPACITY = 16384
#: a part must exceed its baseline by this much, and by the baseline
HOLD_NS = 10_000_000
#: rows of one shape class behind a baseline, and how often it is taken anew
WINDOW, REFRESH = 256, 32
#: a hold waits this many rows for the arrival that sizes it
_PATIENCE = 256
#: how far before a hold's row its last waited-for arrival is looked for
_BEFORE = 64

PARTS = ("admit", "chunks", "grow", "build", "dispatch", "tail",
         "drain_wait", "drain_host", "outside")
COLUMNS = (
    "tick", "rows", "chunk_tokens", "starved", "drained",
    "t_step", "t_admit", "t_chunks", "t_grow", "t_build", "t_dispatch",
    "t_return", "t_end",
    *PARTS, "idle", "idle_before",
    "arrive", "waited",
    "cpu_ns", "runq_ns", "nivcsw", "majflt", "gc_ns", "proc_cpu_ns")
_C = {name: i for i, name in enumerate(COLUMNS)}
(TICK, ROWS, CHUNK_TOKENS, STARVED, DRAINED, T_STEP, T_ADMIT, T_RETURN,
 T_END, T_BUILD) = (_C[k] for k in (
     "tick", "rows", "chunk_tokens", "starved", "drained", "t_step",
     "t_admit", "t_return", "t_end", "t_build"))
D_ADMIT, D_WAIT, D_DRAIN, D_OUTSIDE, D_IDLE, IDLE_BEFORE = (
    _C[k] for k in ("admit", "drain_wait", "drain_host", "outside", "idle",
                    "idle_before"))
ARRIVE, WAITED, CPU_NS, RUNQ_NS, NIVCSW, MAJFLT, GC_NS, PROC_CPU_NS = (
    _C[k] for k in ("arrive", "waited", "cpu_ns", "runq_ns", "nivcsw",
                    "majflt", "gc_ns", "proc_cpu_ns"))
#: the boundaries ``mark`` takes: ``t_admit + b`` and part ``admit + b``
ADMIT, CHUNKS, GROW, BUILD, DISPATCH, RETURN = range(6)
_NPARTS = len(PARTS)
_I_WAIT = PARTS.index("drain_wait")
_BLANK = [0] * len(COLUMNS)
for _k in (TICK, STARVED, DRAINED, WAITED):
    _BLANK[_k] = -1
del _k

class _Median:
    """The median of the last ``WINDOW`` samples, taken anew every ``every``
    samples; ``value`` is None before the first."""

    __slots__ = ("_x", "_every", "n", "value")

    def __init__(self, every: int):
        self._x = np.zeros(WINDOW, np.int64)
        self._every = every
        self.n = 0
        self.value: Optional[int] = None

    def add(self, v: int) -> None:
        n = self.n
        self._x[n % WINDOW] = v
        self.n = n = n + 1
        if n % self._every == 0:
            m = min(n, WINDOW)
            self.value = int(np.sort(self._x[:m])[m // 2])


#: logs the module keeps alive, the newest ones
KEPT = 4
_logs: "Dict[int, TickLog]" = {}


def tick_logs() -> Dict[int, "TickLog"]:
    """The ``KEPT`` newest tick logs by engine id (the ``eng`` of their
    events), whether or not their engines still live."""
    return dict(_logs)


class TickLog:
    """One engine's ring of rows; the module's docstring says what a row
    is. ``clock``, ``counters`` (``read() -> (cpu_ns, runq_ns, nivcsw,
    majflt)``), ``gc_ns`` and ``pressure`` (``read() -> us or None``) are
    the host's own unless a test hands in its own."""

    def __init__(self, eng: int, capacity: int = CAPACITY,
                 clock: Callable[[], int] = time.perf_counter_ns,
                 counters=None, gc_ns: Callable[[], int] = _proc.gc_ns,
                 pressure=None):
        self.eng = int(eng)
        self.capacity = int(capacity)
        self.clock = clock
        self.counters = counters if counters is not None \
            else _proc.ThreadCounters()
        self.gc_ns = gc_ns
        self.pressure = pressure if pressure is not None \
            else _proc.Pressure()
        self._a = np.zeros((self.capacity, len(COLUMNS)), np.int64)
        self._cur: List[int] = list(_BLANK)     # the open row
        self._seq = 0               # rows closed so far; the open row's
        self._open = False
        self._busy = False
        self._gap = D_IDLE          # the part time outside a step goes to
        self._cursor = 0            # the last clock read
        self._step_tick = 0
        self.idle_ns = 0            # idle time of all rows closed so far
        self._c0 = None             # the counters at the row's opening
        # shape class (0: no chunk, 1: a chunk) -> the last WINDOW rows'
        # parts and their host total; what was taken from them
        self._samples = [np.zeros((WINDOW, _NPARTS + 1), np.int64)
                         for _ in (0, 1)]
        self._n_samples = [0, 0]
        self._base: List[Optional[List[int]]] = [None, None]
        # by the arriving tick's class: arrival-to-arrival intervals of
        # consecutive waited-for ticks, and how long the host was blocked
        self._cadence = [_Median(REFRESH // 2) for _ in (0, 1)]
        self._wait = [_Median(REFRESH // 2) for _ in (0, 1)]
        self._got_class = 0         # of the open row's last arrival
        self._asks = False          # the engine says ``starved`` at all
        self._last_arrival = (-2, 0, 0)     # (tick, ns, waited)
        # the last two waited-for arrivals: (tick, ns, idle time until it)
        self._waited_for = [(-2, 0, 0), (-2, 0, 0)]
        self._pending: List[dict] = []      # holds not yet sized
        self._charged_upto = 0
        self._psi: Optional[int] = self.pressure.read()
        self._psi_t = self.clock()
        _logs[self.eng] = self
        while len(_logs) > KEPT:
            del _logs[next(iter(_logs))]

    # --- written by the engine, in this order a step ------------------------
    def enter(self, tick: int) -> None:
        """``step()``'s first line: closes the open row here and opens the
        next. ``tick`` is the number the step will give what it
        dispatches."""
        now = self.clock()
        cur = self._cur
        if self._open:
            cur[self._gap] += now - self._cursor
            self._close(now)
        else:
            self._c0 = self._read_counters()
            self._open = True
        self._cursor = now
        cur[T_STEP] = now
        cur[IDLE_BEFORE] = self.idle_ns
        self._step_tick = tick
        self._gap = D_ADMIT

    def mark(self, b: int) -> int:
        """A boundary: the time since the last clock read was part ``b``'s
        (``ADMIT`` .. ``RETURN``, whose part is ``tail``)."""
        now = self.clock()
        cur = self._cur
        cur[T_ADMIT + b] = now
        cur[D_ADMIT + b] += now - self._cursor
        self._cursor = now
        return now

    def tick(self, tick: int, rows: int, chunk_tokens: int,
             starved: int) -> int:
        """What this step dispatches. Returns the row's number, which
        ``drain_got`` takes when the tick's tokens arrive."""
        cur = self._cur
        cur[TICK], cur[ROWS] = tick, rows
        cur[CHUNK_TOKENS], cur[STARVED] = chunk_tokens, starved
        if starved >= 0:
            self._asks = True
        return self._seq

    def leave(self, busy: bool) -> None:
        """``step()``'s last line. ``busy``: the engine has a live slot or
        a queue, so what follows until the next entry is ``outside`` and
        not its caller's sleep."""
        self.mark(RETURN)
        self._busy = busy
        self._gap = D_OUTSIDE if busy else D_IDLE

    def drain_begin(self) -> None:
        """Before a tick's tokens are asked for, in a step or outside."""
        now = self.clock()
        self._cur[self._gap] += now - self._cursor
        self._cursor = now

    def drain_got(self, row: int, tick: int, waited: bool) -> int:
        """``np.asarray`` returned: tick ``tick`` of row ``row`` has
        arrived. Returns the moment."""
        now = self.clock()
        cur = self._cur
        blocked = now - self._cursor
        cur[D_WAIT if waited else D_DRAIN] += blocked
        self._cursor = now
        cur[DRAINED] = tick
        chunk = 0
        if row >= self._seq:            # its row is still open
            cur[ARRIVE], cur[WAITED] = now, int(waited)
            chunk = cur[CHUNK_TOKENS]
        elif row >= self._seq - self.capacity:  # else the ring went past it
            a, i = self._a, row % self.capacity
            a[i, ARRIVE], a[i, WAITED] = now, int(waited)
            chunk = a[i, CHUNK_TOKENS]
        self._got_class = c = 1 if chunk > 0 else 0
        if waited:
            self._wait[c].add(blocked)
            last_tick, last_t, last_waited = self._last_arrival
            if last_waited and last_tick == tick - 1:
                self._cadence[c].add(now - last_t)
            self._waited_for = [self._waited_for[1],
                                (tick, now, self.idle_ns + cur[D_IDLE])]
        self._last_arrival = (tick, now, int(waited))
        return now

    def drain_end(self) -> None:
        """The token loop is done."""
        now = self.clock()
        self._cur[D_DRAIN] += now - self._cursor
        self._cursor = now

    # --- a row closes -------------------------------------------------------
    def _read_counters(self) -> tuple:
        return self.counters.read() + (self.gc_ns(), time.process_time_ns())

    def _close(self, now: int) -> None:
        cur = self._cur
        cpu0, runq0, sw0, flt0, gc0, proc0 = self._c0
        self._c0 = c1 = self._read_counters()
        if cur[TICK] < 0 and cur[DRAINED] < 0 and not self._busy:
            # an idle engine's spin: its caller's business, no row
            self.idle_ns += now - cur[T_STEP]
            cur[:] = _BLANK
            if self._pending:
                self._resolve(give_up=True)
            return
        cpu1, runq1, sw1, flt1, gc1, proc1 = c1
        cur[T_END] = now
        cur[CPU_NS] = cpu1 - cpu0
        cur[RUNQ_NS] = runq1 - runq0 if runq0 >= 0 <= runq1 else -1
        cur[NIVCSW] = sw1 - sw0 if sw0 >= 0 <= sw1 else -1
        cur[MAJFLT] = flt1 - flt0 if flt0 >= 0 <= flt1 else -1
        cur[GC_NS] = gc1 - gc0
        cur[PROC_CPU_NS] = proc1 - proc0
        if gc1 != gc0:
            _proc.publish()
        self.idle_ns += cur[D_IDLE]
        parts = cur[D_ADMIT:D_ADMIT + _NPARTS]
        wait = parts[_I_WAIT]
        host = sum(parts) - wait
        c = 1 if cur[CHUNK_TOKENS] > 0 else 0
        base = self._base[c]
        # a wait is held against the waits for ticks of the arrived tick's
        # class: a tick with a chunk takes the device longer
        usual = self._wait[self._got_class].value
        if base is not None and host - base[-1] >= max(HOLD_NS, base[-1]):
            self._hold(now, host, base, parts)
        elif usual is not None and wait - usual >= max(HOLD_NS, usual) \
                and self._came_late() >= max(HOLD_NS, usual):
            self._hold(now, wait, None, parts, usual)
        self._a[self._seq % self.capacity] = cur
        self._seq += 1
        if cur[TICK] >= 0:
            parts.append(host)
            n = self._n_samples[c]
            self._samples[c][n % WINDOW] = parts
            self._n_samples[c] = n = n + 1
            if n % REFRESH == 0:
                m = min(n, WINDOW)
                self._base[c] = np.sort(self._samples[c][:m],
                                        axis=0)[m // 2].tolist()
        cur[:] = _BLANK
        if self._pending:
            self._resolve()
        if now - self._psi_t >= 1_000_000_000:
            self._psi, self._psi_t = self.pressure.read(), now

    def _came_late(self) -> int:
        """How far behind the device's own schedule the last waited-for
        tick arrived: against the waited-for arrival before it, the cadence
        of the ticks between and the engine's idle time; 0 where there is
        nothing to hold it against."""
        (tick0, t0, idle0), (tick1, t1, idle1) = self._waited_for
        c = self._got_class
        cadence = self._cadence[c].value or self._cadence[1 - c].value
        if tick0 < 0 or cadence is None:
            return 0
        return (t1 - t0) - (tick1 - tick0) * cadence - (idle1 - idle0)

    def _hold(self, now: int, ns: int, base: Optional[List[int]],
              parts: List[int], usual_wait: int = 0) -> None:
        """The open row carries a hold of ``ns``: what is known of it now.
        ``base``: the row's class's baselines, for a host hold."""
        cur = self._cur
        kind = "host" if base is not None else "device"
        if base is not None:
            excess = ns - base[-1]
            i = max((i for i in range(_NPARTS) if i != _I_WAIT),
                    key=lambda i: parts[i] - base[i])
            where = PARTS[i]
            if where == "outside":
                t0 = cur[T_RETURN]
            elif where == "drain_host":
                t0 = self._last_arrival[1]
            else:               # a part of the step: it ends at its mark
                t0 = cur[T_ADMIT + i] - parts[i]
        else:
            excess = ns - usual_wait
            where = "device_wait"
            t0 = self._last_arrival[1] - parts[_I_WAIT]

        def ms(k):
            return None if cur[k] < 0 else cur[k] / 1e6

        gc_ms, runq_ms = ms(GC_NS), ms(RUNQ_NS)
        hold = {
            "tick": cur[TICK] if cur[TICK] >= 0 else self._step_tick,
            "t0_ns": t0, "ms": ns / 1e6, "excess_ms": excess / 1e6,
            "lost_ms": None, "side": kind, "where": where, "starved": None,
            "cpu_ms": ms(CPU_NS), "proc_cpu_ms": ms(PROC_CPU_NS),
            "runq_ms": runq_ms, "gc_ms": gc_ms,
            "nivcsw": None if cur[NIVCSW] < 0 else cur[NIVCSW],
            "majflt": None if cur[MAJFLT] < 0 else cur[MAJFLT],
            "rows": cur[ROWS], "chunk_tokens": cur[CHUNK_TOKENS],
            "unexplained_ms": excess / 1e6 if kind == "device" else max(
                0.0, (excess - max(cur[GC_NS], 0) - max(cur[RUNQ_NS], 0))
                / 1e6),
        }
        psi = self.pressure.read()
        if psi is not None and self._psi is not None:
            hold["psi_some_ms"] = (psi - self._psi) / 1e3
            hold["psi_age_ms"] = (now - self._psi_t) / 1e6
        self._psi, self._psi_t = psi, now
        hold["_row"] = self._seq
        self._pending.append(hold)

    def _resolve(self, give_up: bool = False) -> None:
        """Sizes the pending holds whose next waited-for tick has arrived
        and emits them; ``give_up``: emits the others too, unsized."""
        still = []
        for hold in self._pending:
            done = self._size(hold)
            if done or give_up or self._seq - hold["_row"] > _PATIENCE:
                self._emit(hold)
            else:
                still.append(hold)
        self._pending = still

    def _size(self, hold: dict) -> bool:
        """``lost_ms`` and ``starved`` of a hold from the rows around it;
        False while the arrival that sizes it is still to come."""
        lo = max(hold["_row"] - _BEFORE, self._seq - self.capacity, 0)
        a = self._a[np.arange(lo, self._seq) % self.capacity]
        t0 = hold["t0_ns"]
        sent = a[(a[:, STARVED] >= 0) & (a[:, T_BUILD] > t0)]
        if len(sent) and hold["where"] != "dispatch":
            hold["starved"] = bool(sent[0, STARVED])
        waited = a[(a[:, WAITED] == 1) & (a[:, TICK] >= 0)]
        # after the hold has ended: the held tick's own late arrival would
        # count as lost what the device ran behind the blocked host
        after = waited[waited[:, ARRIVE] > t0 + int(hold["ms"] * 1e6)]
        if not len(after) or (self._asks and not len(sent)):
            return False        # the arrival, or the dispatch, is to come
        before = waited[waited[:, ARRIVE] <= t0]
        cadence = [m.value for m in self._cadence]
        cadence = [c if c is not None else o
                   for c, o in zip(cadence, reversed(cadence))]
        if not len(before) or None in cadence:
            return True         # nothing to measure from, or against
        nxt, prv = after[0], before[-1]
        if nxt[ARRIVE] <= self._charged_upto:
            hold["lost_ms"] = 0.0       # an earlier hold was charged it
            return True
        between = a[(a[:, TICK] > prv[TICK]) & (a[:, TICK] <= nxt[TICK])]
        owed = int(np.where(between[:, CHUNK_TOKENS] > 0, cadence[1],
                            cadence[0]).sum())
        # the engine's idle time between the two arrivals, by the rows
        # they fell in
        opened = a[:, T_STEP]
        i_prv, i_nxt = (max(int(np.searchsorted(opened, t, "right")) - 1, 0)
                        for t in (prv[ARRIVE], nxt[ARRIVE]))
        idle = int(a[i_nxt, IDLE_BEFORE] - a[i_prv, IDLE_BEFORE])
        span = int(nxt[ARRIVE] - max(prv[ARRIVE], self._charged_upto))
        lost = max(0, span - owed - idle) / 1e6
        if hold["side"] == "host":      # it cannot have cost more than it
            lost = min(lost, hold["excess_ms"])     # took (a host-bound
        hold["lost_ms"] = lost                      # stretch reads long)
        self._charged_upto = int(nxt[ARRIVE])
        return True

    def _emit(self, hold: dict) -> None:
        hold = {k: v for k, v in hold.items() if not k.startswith("_")}
        _events.emit("hold", eng=self.eng, **hold)
        reg = _registry()
        reg.counter("serving/holds{kind=%s}" % hold["side"]).add(1)
        reg.counter("serving/hold_ms{kind=%s}" % hold["side"]).add(
            hold["ms"])
        if hold["lost_ms"]:
            reg.counter("serving/hold_lost_ms").add(hold["lost_ms"])

    def flush(self) -> None:
        """Emits every pending hold, sized if it can be: for a reader that
        comes when the engine has nothing left to drain."""
        if self._pending:
            self._resolve(give_up=True)

    # --- read by whoever holds the log --------------------------------------
    @property
    def total(self) -> int:
        """Rows closed so far, the ones the ring has dropped included."""
        return self._seq

    def rows(self) -> Dict[str, np.ndarray]:
        """The closed rows the ring still holds, oldest first, a column an
        array (copies)."""
        n = min(self._seq, self.capacity)
        a = self._a[np.arange(self._seq - n, self._seq) % self.capacity]
        return {name: a[:, i].copy() for i, name in enumerate(COLUMNS)}

    def reaches_back_to(self, t_ns: int) -> bool:
        """Whether the ring still holds every row since ``t_ns``."""
        if self._seq <= self.capacity:
            return True
        return int(self._a[self._seq % self.capacity, T_STEP]) <= t_ns

    def baselines(self) -> Dict[str, dict]:
        """The running medians, a shape class: ``{"no_chunk" | "chunk":
        {part: ms, "host": ms, "cadence": ms, "wait_for": ms}}`` (the last
        two by the class of the tick that arrived); a class without a
        baseline yet is left out."""
        out = {}
        for c, name in enumerate(("no_chunk", "chunk")):
            base = self._base[c]
            if base is None:
                continue
            out[name] = {p: base[i] / 1e6 for i, p in enumerate(PARTS)}
            out[name]["host"] = base[-1] / 1e6
            for key, m in (("cadence", self._cadence[c]),
                           ("wait_for", self._wait[c])):
                if m.value is not None:
                    out[name][key] = m.value / 1e6
        return out
