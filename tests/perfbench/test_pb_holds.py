"""The six readers of the engine's own record (ISSUE 51) on a synthetic tick
log: a clean window, holds outside the window, holds inside it, a record that
no longer reaches back to the window's opening, and a program without the
record."""
import types

import pytest

from paddle_tpu import profiler
from paddle_tpu.profiler import events, ticklog
from perfbench import loader

LOST = ("served.hold_lost_ms_in_window", "sched.hold_lost_ms_in_window")
UNEXPLAINED = ("served.hold_unexplained_pct", "sched.hold_unexplained_pct")
OUTSIDE = "served.tokens_per_s_outside_holds"
TICK = "served.tick_ms_p50_in_window"
READERS = LOST + UNEXPLAINED + (OUTSIDE, TICK)
MS = 1_000_000
RATE = 4000.0
ENG = iter(range(7000, 8000))


class Clock:
    def __init__(self):
        self.t = 5 * 10 ** 12

    def __call__(self):
        self.t += 1000
        return self.t


class Counters:
    runq = 0

    def read(self):
        return 0, self.runq, 0, 0


class NoPressure:
    def read(self):
        return None


class Served:
    """A tick log driven as an engine with two 5 ms ticks in flight drives
    it, and the run a reader is handed."""

    def __init__(self, monkeypatch, capacity=ticklog.CAPACITY):
        self.clock, self.counters = Clock(), Counters()
        self.log = ticklog.TickLog(next(ENG), capacity=capacity,
                                   clock=self.clock, counters=self.counters,
                                   pressure=NoPressure())
        monkeypatch.setattr(profiler, "tick_logs",
                            lambda: {self.log.eng: self.log})
        self.n, self.inflight, self.free = 0, [], 0
        self.t_open = None

    def step(self, pause_ms=0.0, slow_ms=0.0):
        log, clock = self.log, self.clock
        log.enter(self.n)
        while len(self.inflight) > 2:
            row, tick, done = self.inflight.pop(0)
            log.drain_begin()
            waited = done > clock.t
            clock.t = max(clock.t, done)
            log.drain_got(row, tick, waited)
            log.drain_end()
        clock.t += int(pause_ms * MS)
        for b in (ticklog.ADMIT, ticklog.CHUNKS, ticklog.GROW, ticklog.BUILD):
            log.mark(b)
        starved = self.free <= clock.t
        log.mark(ticklog.DISPATCH)
        self.free = max(self.free, clock.t) + int((5 + slow_ms) * MS)
        self.inflight.append((log.tick(self.n, 4, 0, int(starved)), self.n,
                              self.free))
        self.n += 1
        log.leave(True)

    def steps(self, n, **kw):
        for _ in range(n):
            self.step(**kw)

    def open(self):
        self.t_open = self.clock.t / 1e9

    def run(self):
        seconds = self.clock.t / 1e9 - self.t_open
        return {"ctx": types.SimpleNamespace(t_open=self.t_open,
                                             seconds=seconds),
                "end_to_end": {"serve_tokens_per_s": RATE}, "notes": []}


def read(name, run):
    return loader.load_module("layer_metrics", name).read(run)


@pytest.mark.parametrize("name", READERS)
def test_a_clean_window(monkeypatch, name):
    s = Served(monkeypatch)
    s.steps(100)
    s.open()
    s.steps(200)
    run = s.run()
    value = read(name, run)
    if name in LOST + UNEXPLAINED:
        assert value == 0.0             # a value: a clean side in the ledger
    elif name == OUTSIDE:
        assert value == RATE
    else:
        assert 4.99 < value < 5.01      # the device's 5 ms
    assert any(n.startswith("holds in the window: 0 ") for n in run["notes"])


@pytest.mark.parametrize("name", READERS)
def test_holds_outside_the_window_are_not_counted(monkeypatch, name):
    s = Served(monkeypatch)
    s.steps(100)
    s.step(pause_ms=60)                 # before it opens
    s.steps(20)
    s.open()
    s.steps(200)
    run = s.run()
    s.step(slow_ms=40)                  # after it has closed
    s.steps(10)
    assert len([e for e in events.log().events(kind="hold")
                if e.attrs["eng"] == s.log.eng]) == 2
    value = read(name, run)
    if name in LOST + UNEXPLAINED:
        assert value == 0.0
    elif name == OUTSIDE:
        assert value == RATE


@pytest.mark.parametrize("name", READERS)
def test_the_windows_holds_are_summed_and_told_in_the_notes(monkeypatch,
                                                            name):
    s = Served(monkeypatch)
    s.steps(100)
    s.open()
    s.steps(50)
    s.step(pause_ms=60)                 # starves the device: ~50 ms lost
    s.counters.runq += 45 * MS          # three quarters of it on a run queue
    s.steps(50)
    s.step(slow_ms=30)                  # a device hold: 30 ms, unexplained
    s.steps(100)
    run = s.run()
    value = read(name, run)
    if name in LOST:
        assert 78.0 < value < 86.0, value
    elif name in UNEXPLAINED:           # 15 of 60 and 30 of 30
        assert 49.0 < value < 51.0, value
    elif name == OUTSIDE:
        lost = read(LOST[0], run) / 1e3
        seconds = run["ctx"].seconds
        assert value == pytest.approx(RATE * seconds / (seconds - lost))
        assert value > RATE
    else:
        assert 4.99 < value < 5.01      # a median: two holds do not move it
    table = [n for n in run["notes"] if n.startswith("hold")]
    assert table[0].startswith("holds in the window: 2 (1 host, 1 device)")
    assert "host in admit, starved" in table[1] and "runq 45.0" in table[1]
    assert "device in device_wait" in table[2]
    assert len(table) == 3              # said once, whoever reads first


@pytest.mark.parametrize("name", READERS)
def test_a_record_that_lost_the_windows_beginning_raises(monkeypatch, name):
    # the tick ring
    s = Served(monkeypatch, capacity=64)
    s.steps(20)
    s.open()
    s.steps(200)
    with pytest.raises(RuntimeError, match="tick log keeps 64 rows"):
        read(name, s.run())
    # the event ring
    monkeypatch.setattr(events, "_log", events.EventLog(capacity=4))
    s = Served(monkeypatch)
    s.steps(20)
    s.open()
    for _ in range(6):
        events.emit("submit", rid=1)
    s.steps(20)
    with pytest.raises(RuntimeError, match="event log dropped 2 events"):
        read(name, s.run())


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_record_is_read_as_nothing(monkeypatch, name):
    s = Served(monkeypatch)
    s.steps(50)
    s.open()
    s.steps(50)
    monkeypatch.delattr(profiler, "tick_logs")      # the parent's profiler
    assert read(name, s.run()) is None
