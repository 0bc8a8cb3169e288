"""The serving drive's clock: a time to first token runs from the moment a
request was due, not from the moment the loop got round to submitting it."""
import time

import numpy as np
import pytest

from perfbench import harness, serve_loop as serve


def NOTHING_ON_A_DEVICE(engine):
    """The family's ``device_state``: what ``Drive.mark`` waits for."""
    return ()


class SlowEngine:
    """Stands in for ServingEngine: every step takes ``step_s`` and hands
    each resident request one token. It emits the same ``chunk`` event and
    ``tokens_generated`` counter the drive reads from the real engine."""

    prefill_chunk = 32

    def __init__(self, step_s):
        from paddle_tpu.profiler import events, registry

        self.step_s, self._requests, self._next = step_s, {}, 0
        self.events, self.reg = events, registry()

    def submit(self, prompt, max_new):
        rid, self._next = self._next, self._next + 1
        self._requests[rid] = type("R", (), {"out": [], "max_new": max_new,
                                             "n": len(prompt), "new": True})
        return rid

    def step(self):
        live = [(rid, r) for rid, r in self._requests.items()
                if len(r.out) < r.max_new]
        if not live:
            return False
        time.sleep(self.step_s)
        for rid, r in live:
            if r.new:
                self.events.emit("chunk", rid=rid, start=0, end=r.n)
                r.new = False
            r.out.append(1)
            self.reg.counter("serving/tokens_generated").add(1)
        return True

    def drain(self, target=0):
        pass

    def tokens_so_far(self, rid):
        return tuple(self._requests[rid].out)

    def idle(self):
        return all(len(r.out) >= r.max_new for r in self._requests.values())


def test_ttft_runs_from_the_due_time_when_the_loop_submits_late():
    step_s = 0.05
    plan = {"mode": "open", "warm_in_s": 0.0, "drain_limit_s": 5.0,
            "requests": [
                {"due_s": 0.0, "max_new": 6, "prompt": np.zeros(8, np.int32)},
                # due while the loop is inside its first slow step
                {"due_s": 0.01, "max_new": 3,
                 "prompt": np.zeros(8, np.int32)}]}
    cell = {"cell": {}, "config": {}, "traffic": {}}
    ctx = harness.Context(cell, seed=1, seconds=1.0, trace=False, devices=[])
    drive = serve.Drive(ctx, SlowEngine(step_s), plan, NOTHING_ON_A_DEVICE)
    t0 = time.perf_counter()
    drive.run(t0, lambda now: False)
    r = serve.reduce(plan, drive, t0, t0, 1.0, 8)
    assert r["mine"] == [0, 1] and r["failed"] == []
    late = drive.submit_late[1]
    assert late >= step_s - 0.012              # submitted a step late
    # from the due time: the wait for the loop AND the step that served it
    assert r["ttft"][1] >= (late + step_s) * 1e3 - 1.0
    assert r["ttft"][1] == pytest.approx(
        (drive.token_t[1][0] - (t0 + 0.01)) * 1e3)
    assert r["queue_wait"][1] >= late * 1e3
    assert len(r["gaps"]) == 5 + 2 and min(r["gaps"]) >= step_s * 1e3 - 5
    assert drive.ticks[-1][1] == 16 + 9        # 16 prompt + 9 output tokens
    # holding the traffic clock later (the profiler, after the window)
    # moves no due time that is past
    drive.hold(3.0)
    assert serve.reduce(plan, drive, drive.t0, t0, 1.0, 8)["ttft"] == \
        r["ttft"]
    # positions held by live requests: all 25 once, none when both left
    assert max(x[4] for x in drive.ticks) <= 25 and drive.held == {}


def test_closed_backlog_judges_what_left_the_engine_in_the_window():
    plan = {"mode": "closed", "warm_in_s": 0.0, "drain_limit_s": 0.0,
            "requests": [{"due_s": 0.0, "max_new": n,
                          "prompt": np.zeros(4, np.int32)}
                         for n in (2, 4, 40)]}
    cell = {"cell": {}, "config": {}, "traffic": {}}
    ctx = harness.Context(cell, seed=1, seconds=0.2, trace=False, devices=[])
    drive = serve.Drive(ctx, SlowEngine(0.01), plan, NOTHING_ON_A_DEVICE)
    t0 = time.perf_counter()
    drive.run(t0, lambda now: now >= 0.2)
    assert serve.reduce(plan, drive, t0, t0, 0.2, 8)["mine"] == [0, 1]
    r = serve.reduce(plan, drive, t0, t0 + 0.1, 0.1, 8)
    assert r["mine"] == [] and r["failed"] == []   # 2 is still running
    # all progress over all time: what the last tick at or before the
    # close has counted beyond the last at or before the opening
    a = [x for x in drive.ticks if x[0] <= t0 + 0.1][-1]
    b = [x for x in drive.ticks if x[0] <= t0 + 0.2][-1]
    assert r["serve_tokens_per_s"] == pytest.approx(
        (b[1] - a[1]) / (b[0] - a[0]))
    assert 0.5 / 0.01 < r["serve_tokens_per_s"] <= 1 / 0.01
    assert drive.held == {drive.rid_of[2]: 4 + len(drive.token_t[2])}
