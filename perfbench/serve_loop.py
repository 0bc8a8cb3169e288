"""The serving loop, once: an engine driven through one traffic plan with
the benchmark's own clock on every request and every tick, the window's two
edges, the trace after the close and the reduction to the window's numbers.
``serve_tokens_per_s``, ``ttft_p85_ms`` and ``itl_p95_ms`` are defined here
and nowhere else. What it asks of a family:

``build(ctx) -> (model, engine)``: the engine as the program's users get it;
``warm_up(ctx, engine)``: every program the window can run, run once;
``limits(config) -> {"vocab_size", "capacity", "num_slots"}``: what bounds
    the traffic (tokens, the positions one slot holds) and the cache;
``device_state(engine)``: the arrays an edge of the window waits for, one
    pytree: all the device must have written before host and device agree;
``facts_after(ctx, engine) -> dict``, where the family has them: facts of
    its model that a reader under ``layer_metrics/`` takes.

Of the engine the loop uses ``submit``, ``step``, ``drain``, ``idle``,
``tokens_so_far(rid)``, ``served_weights()`` and ``prefill_chunk``, and from
the program's registry the ``serving/*`` counters and gauges and the request
events ``chunk`` and ``prefix_hit``.
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import loader, yardstick


class Drive:
    """The engine driven through one traffic plan, with the benchmark's
    own clock on every request and every tick."""

    def __init__(self, ctx, eng, plan: dict, device_state):
        from paddle_tpu.profiler import events, registry

        self.ctx, self.eng, self.plan = ctx, eng, plan
        self.device_state = device_state  # family's; ``mark`` waits for it
        self.reg, self.log = registry(), events.log()
        self.cursor = self.log.next_seq
        self.requests = plan["requests"]
        self.rid_of: dict = {}            # request index -> engine rid
        self.index_of: dict = {}          # engine rid -> request index
        self.seen: dict = {}              # rid -> tokens already stamped
        self.live: set = set()
        self.submit_late: dict = {}       # index -> s submitted after due
        self.due_t: dict = {}             # index -> due time, host clock
        self.first_chunk_t: dict = {}     # index -> host time
        self.token_t: dict = {}           # index -> [host time a token]
        self.errors: dict = {}            # index -> message
        #: (t, progress, decode rows, prefill rows, positions held by
        #: live requests, allocated share of the pool's pages)
        self.ticks: list = []
        self.resident = 0                 # prompt tokens made resident
        self.held: dict = {}              # rid -> positions its K/V fills
        self.generated0 = self._generated()

    def _generated(self) -> int:
        return int(self.reg.counter("serving/tokens_generated").value)

    def stamp_tokens(self, t: float) -> None:
        for rid in list(self.live):
            i = self.index_of[rid]
            n = len(self.eng.tokens_so_far(rid))
            if n > self.seen[rid]:
                self.token_t.setdefault(i, []).extend(
                    [t] * (n - self.seen[rid]))
                self.held[rid] = self.held.get(rid, 0) + n - self.seen[rid]
                self.seen[rid] = n
            if n >= self.requests[i]["max_new"]:
                self.live.discard(rid)
                self.held.pop(rid, None)  # its slot is given back

    def _read_events(self, t: float) -> None:
        evs, self.cursor = self.log.since(self.cursor)
        for ev in evs:
            if ev.kind == "chunk":
                got = ev.attrs["end"] - ev.attrs["start"]
                i = self.index_of.get(ev.rid)
                if i is not None:
                    self.first_chunk_t.setdefault(i, t)
            elif ev.kind == "prefix_hit":
                got = ev.attrs["tokens"]
            else:
                continue
            self.resident += got
            if ev.rid in self.live:
                self.held[ev.rid] = self.held.get(ev.rid, 0) + got

    def run(self, t0: float, stop) -> bool:
        """Submit each request when it is due, tick, stamp; until
        ``stop(now_s)`` says so (returns True) or nothing is left to do
        (False). ``t0`` is the traffic clock's zero on the host clock;
        ``hold`` moves it later, and ``self.t0`` is where it ended up."""
        ctx, eng = self.ctx, self.eng
        self.t0 = t0
        pending = 0
        n = len(self.requests)
        while True:
            now = time.perf_counter() - self.t0
            if stop(now):
                return True
            with ctx.span("submit"):
                while pending < n and self.requests[pending]["due_s"] <= now:
                    req = self.requests[pending]
                    try:
                        rid = eng.submit(req["prompt"], req["max_new"])
                    except ValueError as e:     # refused: a failed request
                        self.errors[pending] = str(e)
                    else:
                        self.rid_of[pending] = rid
                        self.index_of[rid] = pending
                        self.seen[rid] = 0
                        self.live.add(rid)
                        self.submit_late[pending] = now - req["due_s"]
                        self.due_t[pending] = self.t0 + req["due_s"]
                    pending += 1
            with ctx.span("step"):
                progressed = eng.step()
            t = time.perf_counter()
            self._read_events(t)
            self.stamp_tokens(t)
            if progressed:
                self._log_tick(t)
                continue
            with ctx.span("drain"):       # nothing to dispatch
                eng.drain(0)
            self.stamp_tokens(time.perf_counter())
            if pending < n:
                wait = self.requests[pending]["due_s"] - \
                    (time.perf_counter() - self.t0)
                time.sleep(min(max(wait, 0.0), 0.002))
            elif eng.idle():
                return False              # nothing left anywhere

    def _log_tick(self, t: float) -> None:
        self.ticks.append((
            t, self.resident + self._generated() - self.generated0,
            self.reg.gauge("serving/mixed_rows_decode").value,
            self.reg.gauge("serving/mixed_rows_prefill").value,
            sum(self.held.values()),
            self.reg.gauge("serving/page_util").value))

    def mark(self) -> float:
        """Waits until the device has done every tick dispatched so far
        and logs the progress as of then: an edge of the window on which
        host and device agree. ``step()`` returns when a tick is
        dispatched, and a tick that hands no token to the host (prefill
        chunks alone) is not waited for, so without this the log can run
        a dozen ticks ahead of the device."""
        import jax

        with self.ctx.span("drain"):
            self.eng.drain(0)
            jax.block_until_ready(self.device_state(self.eng))
        t = time.perf_counter()
        self._read_events(t)
        self.stamp_tokens(t)
        self._log_tick(t)
        return t

    def hold(self, seconds: float) -> None:
        """Stops the traffic clock for ``seconds`` that just went by."""
        self.t0 += seconds

    def output(self, i: int) -> np.ndarray:
        return np.asarray(self.eng.tokens_so_far(self.rid_of[i]), np.int32)

    def done(self, i: int) -> bool:
        return len(self.token_t.get(i, ())) >= self.requests[i]["max_new"]


def due_in_window(plan: dict, seconds: float) -> list:
    """The requests of an open-loop plan that come due inside the window,
    which opens ``warm_in_s`` into the traffic and lasts ``seconds``."""
    warm = plan["warm_in_s"]
    return [i for i, r in enumerate(plan["requests"])
            if warm <= r["due_s"] < warm + seconds]


def reduce(plan: dict, drive: Drive, t0: float, t_open: float,
           seconds: float, slices: int, t_close: float = None) -> dict:
    """From the drive's stamps to the window's numbers. ``t0`` is the
    traffic clock's zero and ``t_open`` the window's opening, both on the
    host clock; the window closes ``seconds`` later, or at ``t_close``
    where the close waited for the device. Open loop: the requests due in
    the window are judged, and a time to first token runs from the due
    time. Closed backlog: the requests that left the engine inside the
    window are."""
    closed = plan["mode"] == "closed"
    if t_close is None:
        t_close = t_open + seconds
    everyone = range(len(plan["requests"]))
    if closed:
        mine = [i for i in everyone if i in drive.errors or (
            drive.done(i) and t_open <= drive.token_t[i][-1] <= t_close)]
    else:
        mine = due_in_window(plan, seconds)
    ttft, queue_wait, gaps = [], [], []
    for i in mine:
        # as the traffic clock stood when it was submitted: a later
        # ``hold`` moves the clock and not what is past
        due = drive.due_t.get(i, t0 + plan["requests"][i]["due_s"])
        if drive.token_t.get(i):
            ttft.append((drive.token_t[i][0] - due) * 1e3)
        if i in drive.first_chunk_t:
            queue_wait.append((drive.first_chunk_t[i] - due) * 1e3)
    for times in drive.token_t.values():
        gaps.extend((b - a) * 1e3 for a, b in zip(times, times[1:])
                    if t_open <= b <= t_close)
    ticks = [x[:2] for x in drive.ticks]
    return {
        "mine": mine, "failed": [i for i in mine if not drive.done(i)],
        "ttft": ttft, "queue_wait": queue_wait, "gaps": gaps,
        "ticks_in_window": [x for x in drive.ticks
                            if t_open <= x[0] <= t_close],
        # all the window's progress over all its time
        "serve_tokens_per_s": yardstick.window_rate(ticks, t_open, t_close),
        "slice_rates": yardstick.slice_rates(ticks, t_open, t_close, slices),
        "slice_p50": yardstick.slice_median_rate(ticks, t_open, t_close,
                                                 slices),
        "output_tokens": sum(sum(t_open <= t <= t_close for t in times)
                             for times in drive.token_t.values()),
    }


def run(ctx, build, warm_up, limits, device_state,
        facts_after=lambda ctx, engine: {}) -> dict:
    """One run of a serving cell; a family binds its own functions."""
    import jax

    lim = limits(ctx.config)
    gen = loader.load_module("generators", ctx.traffic["generator"])
    plan = gen.generate(ctx.traffic, ctx.seed, ctx.seconds, lim)
    model, eng = build(ctx)       # the model lives as long as the run
    warm_up(ctx, eng)

    drive = Drive(ctx, eng, plan, device_state)
    warm, closed = plan["warm_in_s"], plan["mode"] == "closed"
    trace_s = ctx.traffic["traced_s"] if ctx.trace else 0.0
    state = {"opened": None, "closed": None, "trace_until": None,
             "held": 0.0}
    t0 = time.perf_counter()

    judged = [] if closed else due_in_window(plan, ctx.seconds)

    def profiler(switch):
        """Starting and stopping the profiler stalls the loop and the
        device for seconds, so the trace is taken after the window has
        closed, under the same traffic: nothing the window measures sees
        it. The traffic clock is held meanwhile, or every request due in
        the stall would count as late."""
        t = time.perf_counter()
        switch()
        took = time.perf_counter() - t
        drive.hold(took)
        state["held"] += took

    def stop(now):
        if state["opened"] is None:
            if now >= warm:
                if closed:           # the rate's edges wait for the device
                    drive.mark()
                state["opened"] = ctx.open_window()
            return False
        if state["closed"] is None:
            if time.perf_counter() - state["opened"] < ctx.seconds:
                return False
            state["closed"] = drive.mark() if closed \
                else state["opened"] + ctx.seconds
            if trace_s:
                profiler(ctx.start_trace)
                state["trace_until"] = time.perf_counter() + trace_s
        if state["trace_until"]:
            if time.perf_counter() < state["trace_until"]:
                return False
            profiler(ctx.stop_trace)
            state["trace_until"] = None
        if closed:
            return True
        since = time.perf_counter() - state["closed"] - state["held"]
        return since >= plan["drain_limit_s"] or all(
            drive.done(i) or i in drive.errors for i in judged)

    ran_dry = not drive.run(t0, stop) and closed
    t0 = drive.t0
    with ctx.span("drain"):
        eng.drain(0)
    drive.stamp_tokens(time.perf_counter())
    jax.block_until_ready(device_state(eng))
    ctx.stop_trace()
    t_open = state["opened"]
    t_close = state["closed"] or t_open + ctx.seconds

    r = reduce(plan, drive, t0, t_open, ctx.seconds,
               ctx.traffic.get("slices", 8), t_close)
    mine, failed, ttft, gaps = r["mine"], r["failed"], r["ttft"], r["gaps"]
    in_win = r["ticks_in_window"]
    end_to_end = {}
    if closed:
        end_to_end["serve_tokens_per_s"] = r["serve_tokens_per_s"]
    else:
        end_to_end["ttft_p85_ms"] = yardstick.percentile(ttft, 85)
        end_to_end["itl_p95_ms"] = yardstick.percentile(gaps, 95)

    check = loader.load_module("checks", ctx.config["family"])
    finished = [i for i in range(len(plan["requests"])) if drive.done(i)]
    verdict = check.check(ctx, eng.served_weights(), plan, drive, finished)
    compiles = ctx.compiles_in(t_open, t_close)
    late = [drive.submit_late[i] for i in mine
            if i in drive.submit_late] or [0.0]
    facts = {
        "compiles_in_window": compiles, "ticks_in_window": len(in_win),
        "decode_rows_per_tick": float(np.mean([x[2] for x in in_win])),
        "prefill_rows_per_tick": float(np.mean([x[3] for x in in_win])),
        "prefill_chunk": eng.prefill_chunk,
        "queue_wait_ms": r["queue_wait"], "ttft_ms": ttft, "itl_ms": gaps,
        "serve_tokens_per_s_slice_p50": r["slice_p50"],
        "output_tokens_per_s": r["output_tokens"] / ctx.seconds,
        "generator_late_ms_max": max(late) * 1e3,
        # of the pools' slots x capacity positions, the share that holds a
        # live request's tokens, mean over the window's ticks
        "live_kv_share": float(np.mean([x[4] for x in in_win]))
        / (lim["num_slots"] * lim["capacity"]),
        **facts_after(ctx, eng),
    }
    notes = [
        f"{len(mine)} requests judged, {len(failed)} failed; "
        f"{len(in_win)} ticks in the window; generator late at most "
        f"{max(late) * 1e3:.1f} ms; output {facts['output_tokens_per_s']:.2f}"
        f" tokens/s; {verdict['note']}",
        f"prompt + output tokens {r['serve_tokens_per_s']:.2f} a second "
        f"over the whole window; median of {len(r['slice_rates'])} slices "
        f"{r['slice_p50']:.2f}; slices "
        + " ".join(f"{x:.1f}" for x in r["slice_rates"]),
        f"live requests hold {100 * facts['live_kv_share']:.1f} % of the "
        f"pools' positions; pages allocated (prefix cache's included) "
        f"{100 * float(np.mean([x[5] for x in in_win])):.1f} %"]
    if ttft:
        notes.append(
            "ttft p50 / p85 / p90 "
            + " / ".join(f"{yardstick.percentile(ttft, p):.1f}"
                         for p in (50, 85, 90))
            + f" ms of {len(ttft)}; itl p50 "
            f"{yardstick.percentile(gaps, 50):.2f} ms of {len(gaps)} gaps")
    if ran_dry:
        notes.append("the engine ran out of work before the window closed: "
                     "the traffic file queues too few requests")
    return {"correct": verdict["ok"] and compiles == 0 and not ran_dry,
            "attempted": len(mine), "failed": len(failed),
            "end_to_end": end_to_end, "facts": facts, "notes": notes}
