"""The engine's record of every tick and the holds it names (ISSUE 51;
``profiler/ticklog.py``, ``profiler/proc.py``): on a toy engine with the
clock and the thread's counters handed in, so a "pause" is a jump of the
clock and costs no time."""
import gc
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.profiler import events, proc, registry, ticklog
from paddle_tpu.serving import ServingConfig, ServingEngine

MS = 1_000_000


class Clock:
    """A microsecond a read; ``pause`` is what a hold looks like."""

    def __init__(self):
        self.t = 10 ** 12

    def __call__(self) -> int:
        self.t += 1000
        return self.t

    def pause(self, ms: float) -> None:
        self.t += int(ms * MS)


class Counters:
    """The thread's counters as a test sets them."""

    def __init__(self, runq: int = 0):
        self.cpu, self.runq, self.nivcsw, self.majflt = 0, runq, 0, 0

    def read(self):
        return self.cpu, self.runq, self.nivcsw, self.majflt


class NoPressure:
    def read(self):
        return None


def fake_log(eng: int, **kw):
    clock = Clock()
    kw.setdefault("counters", Counters())
    return ticklog.TickLog(eng, clock=clock, pressure=NoPressure(),
                           **kw), clock


def holds_since(seq: int, eng: int) -> list:
    return [e.attrs for e in events.log().events(kind="hold", since_seq=seq)
            if e.attrs["eng"] == eng]


# --- the log alone, driven as the engine drives it ---------------------------
class Driven:
    """A log driven through steps as ``ServingEngine.step`` drives it, two
    ticks in flight; the "device" takes ``device_ms`` a tick and runs them
    in order, so arrivals and ``waited`` are worked out, not observed."""

    def __init__(self, eng: int, device_ms: float = 5.0, **kw):
        self.log, self.clock = fake_log(eng, **kw)
        self.device_ns = int(device_ms * MS)
        self.n = 0
        self.inflight = []              # (row, tick, device done at)
        self.device_free = 0

    def step(self, pause_in=None, pause_ms=0.0, chunk=0, slow_ms=0.0,
             drained=True, stall_ms=0.0):
        log, clock = self.log, self.clock
        log.enter(self.n)
        while len(self.inflight) > 2:
            row, tick, done = self.inflight.pop(0)
            log.drain_begin()
            waited = done > clock.t
            clock.t = max(clock.t, done)
            if waited:      # the whole process stops; the device runs on
                clock.pause(stall_ms)
            if pause_in == "drain_host" and not waited:
                clock.pause(pause_ms)
            log.drain_got(row, tick, waited)
            if pause_in == "drain_host" and waited:
                clock.pause(pause_ms)
            log.drain_end()
        for b, name in ((ticklog.ADMIT, "admit"), (ticklog.CHUNKS, "chunks"),
                        (ticklog.GROW, "grow"), (ticklog.BUILD, "build")):
            if pause_in == name:
                clock.pause(pause_ms)
            log.mark(b)
        starved = self.device_free <= clock.t
        if pause_in == "dispatch":
            clock.pause(pause_ms)
        log.mark(ticklog.DISPATCH)
        start = max(self.device_free, clock.t)
        self.device_free = start + self.device_ns + int(slow_ms * MS)
        row = log.tick(self.n, 4, chunk, int(starved))
        if drained:         # a tick of prefill chunks alone hands out nothing
            self.inflight.append((row, self.n, self.device_free))
        self.n += 1
        if pause_in == "tail":
            clock.pause(pause_ms)
        log.leave(True)
        if pause_in == "outside":
            clock.pause(pause_ms)

    def steps(self, n: int, **kw):
        for _ in range(n):
            self.step(**kw)


def test_the_parts_sum_to_the_step_to_step_interval():
    d = Driven(9001)
    d.steps(50)
    d.step(pause_in="build", pause_ms=3)
    d.steps(5)
    r = d.log.rows()
    total = sum(r[p] for p in ticklog.PARTS) + r["idle"]
    assert (total == r["t_end"] - r["t_step"]).all()
    assert (r["t_step"][1:] == r["t_end"][:-1]).all()
    # the boundaries are the parts' ends, in the spans' order
    for a, b in zip(("t_step", "t_admit", "t_chunks", "t_grow", "t_build",
                     "t_dispatch", "t_return"),
                    ("t_admit", "t_chunks", "t_grow", "t_build",
                     "t_dispatch", "t_return", "t_end")):
        assert (r[a] < r[b]).all(), (a, b)
    assert (r["t_build"] - r["t_grow"] == r["build"]).all()
    assert r["build"].max() == 3 * MS + 1000


def test_a_hold_the_queued_ticks_hid_lost_nothing():
    """Two 20 ms ticks are queued: a 15 ms pause of the host costs the
    device no time, and the hold says so."""
    d = Driven(9002, device_ms=20.0)
    d.steps(80)
    seq = events.log().next_seq
    d.step(pause_in="admit", pause_ms=15)
    d.steps(6)
    (h,) = holds_since(seq, 9002)
    assert h["side"] == "host" and h["where"] == "admit"
    assert h["starved"] is False
    assert h["lost_ms"] == 0.0
    assert 14.9 < h["excess_ms"] < 15.1 and h["ms"] > h["excess_ms"]


def test_a_hold_that_starved_the_device_lost_what_the_device_idled():
    d = Driven(9003, device_ms=5.0)
    d.steps(80)
    seq = events.log().next_seq
    d.step(pause_in="grow", pause_ms=60)
    d.steps(6)
    (h,) = holds_since(seq, 9003)
    assert (h["side"], h["where"], h["starved"]) == ("host", "grow", True)
    # two 5 ms ticks were queued or running when the pause began
    assert 49.0 < h["lost_ms"] < 56.0, h
    assert h["lost_ms"] <= h["excess_ms"]
    reg = registry()
    assert reg.counter("serving/holds{kind=host}").value >= 1
    assert reg.counter("serving/hold_lost_ms").value >= h["lost_ms"]


def test_a_slow_tick_is_a_device_hold_and_unexplained_whole():
    d = Driven(9004, device_ms=5.0)
    d.steps(80)
    seq = events.log().next_seq
    d.step(slow_ms=40)
    d.steps(8)
    (h,) = holds_since(seq, 9004)
    assert h["side"] == "device" and h["where"] == "device_wait"
    assert 39.0 < h["excess_ms"] < 41.0
    assert h["unexplained_ms"] == h["excess_ms"]
    assert 38.0 < h["lost_ms"] < 41.0, h


def test_a_stall_behind_a_blocked_host_loses_what_the_device_idled():
    """The process stops for 60 ms while the host waits for a tick: the
    tick's tokens come 60 ms late, but the device ran the queued tick
    meanwhile, and that much was not lost."""
    d = Driven(9012, device_ms=20.0)
    d.steps(80)
    seq = events.log().next_seq
    d.step(stall_ms=60)
    d.steps(8)
    (h,) = holds_since(seq, 9012)
    assert h["side"] == "device" and 59.0 < h["excess_ms"] < 61.0
    # the held tick and the one queued behind it: up to 40 ms of work
    assert 19.0 < h["lost_ms"] < 45.0, h


def test_a_wait_for_a_tick_with_a_chunk_is_held_against_its_own_class():
    """A tick with a chunk takes the device longer: the wait for it is no
    hold against the waits for ticks without one."""
    d = Driven(9005, device_ms=5.0)
    d.steps(80)
    seq = events.log().next_seq
    d.step(chunk=256, slow_ms=40)       # a heavier tick, legitimately
    d.steps(8)
    assert holds_since(seq, 9005) == []
    d.step(slow_ms=40)                  # the same wait for a plain tick
    d.steps(8)
    assert [h["side"] for h in holds_since(seq, 9005)] == ["device"]


def test_a_long_wait_after_the_host_ran_ahead_is_no_hold():
    """Ticks of prefill chunks alone are not drained, so the host runs
    ahead of the device through them; the wait for the next drained tick is
    long, the device is on schedule, and nothing is named."""
    d = Driven(9010, device_ms=5.0)
    d.steps(80)
    seq = events.log().next_seq
    d.steps(8, drained=False)           # 40 ms of work queued, none waited for
    d.steps(8)
    assert d.log.rows()["drain_wait"].max() > 30 * MS
    assert holds_since(seq, 9010) == []


def test_a_hold_inside_dispatch_cannot_say_starved():
    d = Driven(9011, device_ms=5.0)
    d.steps(80)
    seq = events.log().next_seq
    d.step(pause_in="dispatch", pause_ms=60)
    d.steps(6)
    (h,) = holds_since(seq, 9011)
    assert h["where"] == "dispatch" and h["starved"] is None
    assert 45.0 < h["lost_ms"] <= h["excess_ms"]


def test_what_the_counters_cover_is_explained():
    counters = Counters()
    d = Driven(9006, counters=counters)
    d.steps(80)
    seq = events.log().next_seq
    d.step(pause_in="outside", pause_ms=50)
    counters.runq += 30 * MS            # where that pause went, in part
    counters.nivcsw += 3
    d.steps(6)
    (h,) = holds_since(seq, 9006)
    assert h["where"] == "outside" and h["runq_ms"] == 30.0
    assert h["nivcsw"] == 3 and h["gc_ms"] == 0.0
    assert 19.9 < h["unexplained_ms"] < 20.1


def test_a_missing_schedstat_reads_as_unknown_never_zero(monkeypatch):
    monkeypatch.setattr(proc.ThreadCounters, "SCHEDSTAT",
                        "/proc/thread-self/no-such-file")
    counters = proc.ThreadCounters()
    cpu, runq, nivcsw, majflt = counters.read()
    assert runq == proc.UNKNOWN and cpu > 0
    d = Driven(9007, counters=counters)
    d.steps(80)
    seq = events.log().next_seq
    d.step(pause_in="chunks", pause_ms=50)
    d.steps(6)
    assert (d.log.rows()["runq_ns"] == proc.UNKNOWN).all()
    (h,) = holds_since(seq, 9007)
    assert h["runq_ms"] is None
    assert h["unexplained_ms"] == h["excess_ms"]    # nothing known covers it


def test_the_ring_wraps_in_place():
    d = Driven(9008, capacity=64)
    ring = d.log._a
    d.steps(40)
    early = d.log.rows()["t_step"][0]
    assert d.log.reaches_back_to(early)
    d.steps(160)
    assert d.log._a is ring and ring.shape == (64, len(ticklog.COLUMNS))
    r = d.log.rows()
    assert d.log.total == 199 and len(r["tick"]) == 64     # one is open
    assert list(r["tick"]) == list(range(135, 199))
    assert not d.log.reaches_back_to(early)
    assert d.log.reaches_back_to(int(r["t_step"][0]))


def test_an_idle_engines_spin_keeps_no_row_and_owes_nothing():
    log, clock = fake_log(9009)
    for n in range(3):
        log.enter(n)
        log.mark(ticklog.ADMIT)
        log.tick(n, 1, 0, 1)
        log.leave(n < 2)                # the last leaves the engine empty
    for _ in range(50):                 # its caller polls it
        clock.pause(2)
        log.enter(3)
        log.leave(False)
    log.enter(3)
    r = log.rows()
    assert log.total == 3 and list(r["tick"]) == [0, 1, 2]
    assert r["outside"][2] == 0 and r["idle"][2] > 0
    assert log.idle_ns >= 50 * 2 * MS


# --- on an engine ------------------------------------------------------------
class Toy:
    def __init__(self):
        paddle.seed(0)
        net = GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                            num_heads=2, max_seq_len=512))
        net.eval()
        self.eng = ServingEngine(net, ServingConfig(num_slots=2,
                                                    page_size=16))
        self.id = self.eng._eng_id
        self.counters = Counters()
        self.eng._ticks, self.clock = fake_log(self.id,
                                               counters=self.counters)
        self.log = self.eng.tick_log()

    def steps(self, n: int) -> None:
        for _ in range(n):
            if self.eng.idle():
                self.eng.submit(np.arange(5, dtype=np.int32), 400)
            if not self.eng.step():     # its last ticks: as ``run()`` does
                self.eng.drain(0)

    def pause_inside(self, monkeypatch, obj, name: str, ms: float):
        """The next call of ``obj.name`` takes ``ms`` longer."""
        real = getattr(obj, name)

        def once(*a, **k):
            monkeypatch.setattr(obj, name, real)
            self.clock.pause(ms)
            return real(*a, **k)

        monkeypatch.setattr(obj, name, once)


@pytest.fixture(scope="module")
def toy():
    t = Toy()
    t.steps(60)                         # a baseline for ticks with no chunk
    assert "no_chunk" in t.log.baselines()
    return t


class HeldTokens:
    """A tick's tokens that take ``ms`` to reach the host."""

    def __init__(self, tok, clock, ms, ready):
        self.tok, self.clock, self.ms, self.ready = tok, clock, ms, ready

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        self.clock.pause(self.ms)
        return np.asarray(self.tok)


PLANTED = {
    "admit": lambda t: (t.eng, "_admit"),
    "chunks": lambda t: (t.eng, "_collect_chunks"),
    "grow": lambda t: (t.eng, "_grow_pages"),
    "build": lambda t: (t.eng, "_build_unified"),
    "dispatch": lambda t: (t.eng, "_run_tick"),
    "tail": lambda t: (t.eng.pool, "live_shares"),
}


@pytest.mark.parametrize("where", sorted(PLANTED) + ["drain_host",
                                                      "outside"])
def test_a_planted_pause_is_found_once_where_it_was(toy, monkeypatch, where):
    seq = events.log().next_seq
    if where == "outside":
        toy.clock.pause(50)
    elif where == "drain_host":
        ent = toy.eng._inflight[0]
        toy.eng._inflight[0] = ent._replace(
            tok=HeldTokens(ent.tok, toy.clock, 50, ready=True))
    else:
        toy.pause_inside(monkeypatch, *PLANTED[where](toy), 50)
    toy.steps(8)
    toy.log.flush()
    (h,) = holds_since(seq, toy.id)
    assert (h["side"], h["where"]) == ("host", where), h
    assert 49.9 < h["excess_ms"] < 50.1 and h["chunk_tokens"] == 0
    assert h["starved"] in ((None,) if where == "dispatch"
                            else (True, False))
    row = toy.log.rows()
    at = int(np.argmax(row[where]))
    assert row[where][at] >= 50 * MS
    if where != "outside":
        assert h["tick"] == row["tick"][at]
    else:           # found when the next step began, laid to the tick before
        assert h["tick"] == row["tick"][at]


def test_a_pause_under_the_threshold_is_no_hold(toy, monkeypatch):
    seq = events.log().next_seq
    toy.pause_inside(monkeypatch, toy.eng, "_admit", 5)
    toy.steps(6)
    toy.log.flush()
    assert holds_since(seq, toy.id) == []


def test_a_held_tick_is_a_device_hold(toy):
    def hold_next(ms):
        while len(toy.eng._inflight) < 2:       # between two requests
            toy.steps(1)
        ent = toy.eng._inflight[0]
        toy.eng._inflight[0] = ent._replace(
            tok=HeldTokens(ent.tok, toy.clock, ms, ready=False))
        toy.steps(1)

    # the toy's device is mostly done before the host asks, so the usual
    # wait is planted too: 0.2 ms, until the baseline's window holds no other
    for _ in range(ticklog.WINDOW + ticklog.REFRESH):
        hold_next(0.2)
    assert 0.19 < toy.log.baselines()["no_chunk"]["wait_for"] < 0.22
    def next_has_a_chunk():
        r = toy.log.rows()
        return r["chunk_tokens"][r["tick"] == toy.eng._inflight[0].tick][0]

    while len(toy.eng._inflight) < 2 or next_has_a_chunk():
        toy.steps(1)                    # a new request's prompt: let it by
    seq = events.log().next_seq
    hold_next(50)
    toy.steps(4)
    toy.log.flush()
    (h,) = holds_since(seq, toy.id)
    assert (h["side"], h["where"]) == ("device", "device_wait")
    assert h["unexplained_ms"] == h["excess_ms"] > 49


def test_starved_says_whether_the_device_had_run_dry(toy, monkeypatch):
    # dry: the pause lasts until the device has nothing left
    seq = events.log().next_seq
    real = toy.eng._admit

    def wait_for_the_device():
        monkeypatch.setattr(toy.eng, "_admit", real)
        toy.eng._last_tok.block_until_ready()
        toy.clock.pause(50)
        real()

    monkeypatch.setattr(toy.eng, "_admit", wait_for_the_device)
    toy.steps(6)
    toy.log.flush()
    (h,) = holds_since(seq, toy.id)
    assert h["where"] == "admit" and h["starved"] is True

    # not dry: something queued before the tick is still running
    @jax.jit
    def slow(tok):
        x = jnp.ones((600, 600), jnp.float32)
        x = jax.lax.fori_loop(0, 60, lambda i, x: x @ x / 600.0, x)
        return tok + (x[0, 0] * 0).astype(tok.dtype)

    slow(toy.eng._last_tok).block_until_ready()     # compiled
    seq = events.log().next_seq
    real_build = toy.eng._build_unified

    def queue_work_then_pause(*a, **k):
        monkeypatch.setattr(toy.eng, "_build_unified", real_build)
        toy.clock.pause(50)
        toy.eng._last_tok = slow(toy.eng._last_tok)
        return real_build(*a, **k)

    monkeypatch.setattr(toy.eng, "_build_unified", queue_work_then_pause)
    toy.steps(6)
    toy.log.flush()
    (h,) = holds_since(seq, toy.id)
    assert h["where"] == "build" and h["starved"] is False


def test_a_collection_inside_a_step_is_on_the_record(monkeypatch):
    toy = Toy()
    toy.eng._ticks = ticklog.TickLog(toy.id, clock=toy.clock,
                                     counters=toy.counters,
                                     pressure=NoPressure())     # real gc_ns
    toy.steps(3)
    reg = registry()
    proc.publish()
    before = reg.counter("proc/gc_collections{gen=2}").value
    ms = reg.counter("proc/gc_ms{gen=2}").value
    real = toy.eng._admit

    def collect():
        monkeypatch.setattr(toy.eng, "_admit", real)
        gc.collect()
        real()

    monkeypatch.setattr(toy.eng, "_admit", collect)
    tick = toy.eng._tick_no
    toy.steps(2)
    assert reg.counter("proc/gc_collections{gen=2}").value == before + 1
    took = reg.counter("proc/gc_ms{gen=2}").value - ms
    r = toy.eng.tick_log().rows()
    (at,) = np.nonzero(r["tick"] == tick)[0]
    assert r["gc_ns"][at] > 0 and took > 0
    assert abs(r["gc_ns"][at] / 1e6 - took) < 0.5 + 0.1 * took
    assert r["gc_ns"][at] == r["gc_ns"].max()


def test_the_collectors_counters_are_in_the_summary_after_a_reset():
    profiler.summary()
    profiler.reset()
    assert not [n for n in registry().names() if n.startswith("proc/gc")]
    gc.collect()
    got = profiler.summary()["metrics"]
    assert got["proc/gc_collections{gen=2}"]["value"] == 1
    assert got["proc/gc_ms{gen=2}"]["value"] > 0


def test_the_newest_logs_are_found_by_engine_id_and_outlive_their_engine():
    """A benchmark's reader comes when the engine is garbage: the module
    keeps the newest logs itself, and only those."""
    toy = Toy()
    eng_id, log = toy.id, toy.eng.tick_log()
    assert profiler.tick_logs()[eng_id] is log
    engine = weakref.ref(toy.eng)
    del toy
    gc.collect()
    assert engine() is None and profiler.tick_logs()[eng_id] is log
    for k in range(ticklog.KEPT):
        fake_log(9100 + k)
    assert eng_id not in profiler.tick_logs()
    assert len(profiler.tick_logs()) == ticklog.KEPT


def test_an_engines_own_clock_is_the_spans_and_the_events():
    """With nothing handed in the log reads ``perf_counter_ns``, the clock of
    the event log, and the thread's own counters."""
    import time

    paddle.seed(0)
    net = GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=64))
    net.eval()
    eng = ServingEngine(net, ServingConfig(num_slots=2, page_size=16))
    t0 = time.perf_counter_ns()
    eng.submit(np.arange(5, dtype=np.int32), 6)
    eng.run()
    t1 = time.perf_counter_ns()
    r = eng.tick_log().rows()
    assert list(r["tick"][r["tick"] >= 0]) == list(range(6))
    assert t0 < r["t_step"][0] and r["t_end"][-1] < t1
    assert (r["cpu_ns"] > 0).all()
    drained = r["arrive"] > 0
    assert drained.sum() == 6 and (r["waited"][drained] >= 0).all()
    assert (r["arrive"][drained] > r["t_dispatch"][drained]).all()
