"""What the readers of the engine's own record share (``paddle_tpu/profiler/
ticklog.py``: one row a tick, always on, and a ``hold`` event for every stall
the engine named): the judged window's holds and rows, taken in the run's own
process from ``events.log()`` and ``profiler.tick_logs()``.

The profiler is on for a few seconds after the window has closed, so every
other per-layer reader sees a stretch nobody judges. These see the window
itself: ``[ctx.t_open, ctx.t_open + ctx.seconds]`` on ``perf_counter``'s
clock, which is the event log's and the tick log's.

A program without the record (the parent of the PR that brought it) has no
``profiler.tick_logs``: every reader here then returns ``None`` and raises
nothing. A record that no longer reaches back to the window's opening (the
event ring or the tick ring has dropped what the window began with) raises:
a table with its first rows missing would read as a clean window.
"""
from __future__ import annotations

import statistics
from typing import Optional


def record(run) -> Optional[dict]:
    """``{"holds": [attrs of each hold of the window], "rows": {column:
    array} of the engine's kept rows, "t0", "t1" (ns), "seconds"}``, once a
    run; the window's hold table goes into the run's notes."""
    if "_holds" in run:
        return run["_holds"]
    run["_holds"] = None
    try:
        from paddle_tpu import profiler
        from paddle_tpu.profiler import events
    except ImportError:
        return None
    logs = getattr(profiler, "tick_logs", lambda: {})()
    ctx = run["ctx"]
    if not logs or ctx.t_open is None:
        return None
    t0 = int(ctx.t_open * 1e9)
    t1 = t0 + int(ctx.seconds * 1e9)

    def ticks_in_window(log):
        opened = log.rows()["t_step"]
        return int(((opened >= t0) & (opened <= t1)).sum())

    log = max(logs.values(), key=ticks_in_window) if len(logs) > 1 \
        else next(iter(logs.values()))              # the run's engine
    log.flush()
    elog = events.log()
    kept = elog.events()
    if not log.reaches_back_to(t0):
        raise RuntimeError(
            f"the tick log keeps {log.capacity} rows and the oldest is "
            f"younger than the window's opening: {log.total} ticks so far")
    if elog.dropped and kept and kept[0].t_ns > t0:
        raise RuntimeError(
            f"the event log dropped {elog.dropped} events and the oldest "
            "kept is younger than the window's opening")
    holds = [e.attrs for e in kept if e.kind == "hold"
             and e.attrs.get("eng") == log.eng
             and t0 <= e.attrs["t0_ns"] <= t1]
    out = run["_holds"] = {"holds": holds, "rows": log.rows(), "t0": t0,
                           "t1": t1, "seconds": float(ctx.seconds)}
    run.setdefault("notes", []).extend(table(out))
    return out


def _ms(v, digits=1) -> str:
    return "?" if v is None else f"{v:.{digits}f}"


def table(rec: dict) -> list:
    """The window's holds, one line each, under one line of totals."""
    holds, rows = rec["holds"], rec["rows"]
    inside = (rows["t_step"] >= rec["t0"]) & (rows["t_step"] <= rec["t1"])
    ticks = inside & (rows["tick"] >= 0)
    lines = [
        f"holds in the window: {len(holds)} "
        f"({sum(h['side'] == 'host' for h in holds)} host, "
        f"{sum(h['side'] == 'device' for h in holds)} device), lost "
        f"{sum(h['lost_ms'] or 0.0 for h in holds):.1f} ms of "
        f"{rec['seconds'] * 1e3:.0f}; {int(ticks.sum())} ticks, "
        f"{int((ticks & (rows['waited'] == 1)).sum())} waited for; "
        f"collector {rows['gc_ns'][inside].sum() / 1e6:.1f} ms, run queue "
        + ("?" if (rows["runq_ns"][inside] < 0).all()
           else f"{rows['runq_ns'][inside].clip(0).sum() / 1e6:.1f}")
        + f" ms, major faults {int(rows['majflt'][inside].clip(0).sum())}"]
    for h in holds:
        lines.append(
            f"hold at {(h['t0_ns'] - rec['t0']) / 1e9:.3f} s tick "
            f"{h['tick']}: {h['ms']:.1f} ms ({h['excess_ms']:.1f} over), "
            f"{h['side']} in {h['where']}, "
            + ("starved" if h["starved"] else "hidden" if
               h["starved"] is False else "starved ?")
            + f", lost {_ms(h['lost_ms'])} ms; cpu {_ms(h['cpu_ms'])} (process "
            f"{_ms(h.get('proc_cpu_ms'))}) runq "
            f"{_ms(h['runq_ms'])} gc {_ms(h['gc_ms'])} ms, switches "
            f"{h['nivcsw']} faults {h['majflt']}, unexplained "
            f"{h['unexplained_ms']:.1f} ms"
            + (f", machine's cpu pressure {h['psi_some_ms']:.1f} ms in "
               f"{h['psi_age_ms']:.0f}" if "psi_some_ms" in h else ""))
    return lines


def lost_ms(run) -> Optional[float]:
    """What the window's holds cost on the device's side, summed; 0.0 for a
    clean window."""
    rec = record(run)
    if rec is None:
        return None
    return float(sum(h["lost_ms"] or 0.0 for h in rec["holds"]))


def unexplained_pct(run) -> Optional[float]:
    """Of the excess of all the window's holds, the share that neither the
    collector nor the run queue covers (a device hold whole). 0.0 for a
    window without a hold: nothing there is left unexplained, and the line of
    a traced run has to carry every metric of its cell (``None`` reads as a
    program without the record, which is the parent's answer alone)."""
    rec = record(run)
    if rec is None:
        return None
    excess = sum(h["excess_ms"] for h in rec["holds"])
    if not excess:
        return 0.0
    return 100.0 * sum(h["unexplained_ms"] for h in rec["holds"]) / excess


def tokens_per_s_outside(run) -> Optional[float]:
    """The run's own ``serve_tokens_per_s`` over the window less what its
    holds lost: the rate it would have read without them."""
    rate = run["end_to_end"].get("serve_tokens_per_s")
    lost = lost_ms(run)
    if rate is None or lost is None:
        return None
    seconds = run["_holds"]["seconds"]
    return rate * seconds / (seconds - lost / 1e3)


def tick_ms_p50(run) -> Optional[float]:
    """Median arrival-to-arrival interval over consecutive waited-for ticks
    of the window: the device's tick over the whole window, seen without a
    trace."""
    rec = record(run)
    if rec is None:
        return None
    rows = rec["rows"]
    sent = rows["tick"] >= 0
    tick, arrive, waited = (rows[k][sent]
                            for k in ("tick", "arrive", "waited"))
    pair = (tick[1:] == tick[:-1] + 1) & (waited[1:] == 1) \
        & (waited[:-1] == 1) & (arrive[1:] >= rec["t0"]) \
        & (arrive[1:] <= rec["t1"])
    gaps = (arrive[1:] - arrive[:-1])[pair]
    return statistics.median(gaps.tolist()) / 1e6 if len(gaps) else None
