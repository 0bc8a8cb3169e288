"""What stalled a serving cell inside its judged window, by the engine's own
record (``paddle_tpu/profiler/ticklog.py``):

    python3 benchmarks/holds.py <cell> --seed n --runs k [--trace 0|1]

Each run is a process of its own that calls ``perfbench.run.main`` unchanged
(seeds ``n``, ``n + 1``, ...; the chip belongs to one process at a time, and
a second run in one process would not start from the heap the driver's runs
start from) and then reads what that process recorded: the window's ``hold``
events, the collector's counters, and the tick log's parts. It prints the
hold table (``perfbench/layer_metrics/_holds.py``, the per-layer readers'
own) and one line ``[holds] {json}`` a run; the last line sums the runs up.
Also an example of reading the record: ``profiler.tick_logs()``,
``events.log().events(kind="hold")``, ``registry().snapshot()``.

Needs the cell's chips, as ``perfbench/run.py`` does.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import statistics
import subprocess
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MARK = "[holds] "


class _Tee(io.TextIOBase):
    def __init__(self, out):
        self.out, self.lines = out, []

    def write(self, s):
        self.lines.append(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()


def one_run(cell: str, seed: int, seconds: float, trace: int) -> int:
    """This process runs the cell once, then says what it recorded."""
    sys.path.insert(0, ROOT)
    from perfbench import harness, loader, run

    window = {}
    open_window = harness.Context.open_window

    def remember(ctx):
        window["t_open"] = open_window(ctx)
        window["seconds"] = ctx.seconds
        return window["t_open"]

    harness.Context.open_window = remember
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)])
    if rc or "t_open" not in window:
        return rc or 1
    line = json.loads("".join(tee.lines).strip().splitlines()[-1])

    from paddle_tpu.profiler import registry, ticklog

    rate = line["metrics"].get("serve_tokens_per_s", {}).get("value")
    record = types.SimpleNamespace(**window)
    ran = {"ctx": record, "end_to_end": {"serve_tokens_per_s": rate},
           "notes": []}
    holds = loader.load_module("layer_metrics", "_holds")
    rec = holds.record(ran)
    for note in ran["notes"]:
        print(MARK.strip(), note, flush=True)
    rows = rec["rows"]
    for h in rec["holds"]:          # the rows about each hold, ms
        at = int((rows["t_step"] <= h["t0_ns"]).sum()) - 1
        for i in range(max(at - 6, 0), min(at + 5, len(rows["tick"]))):
            print(MARK.strip(), "row" + (" *" if i == at else "  "),
                  " ".join(f"{k}={int(rows[k][i])}" for k in (
                      "tick", "rows", "chunk_tokens", "starved", "waited",
                      "drained")),
                  f"opened={(rows['t_step'][i] - rec['t0']) / 1e6:.2f}",
                  f"arrive={(rows['arrive'][i] - rec['t0']) / 1e6:.2f}",
                  " ".join(f"{p}={rows[p][i] / 1e6:.2f}"
                           for p in ticklog.PARTS + ("idle", "cpu_ns", "proc_cpu_ns")),
                  flush=True)
    inside = (rows["t_step"] >= rec["t0"]) & (rows["t_step"] <= rec["t1"]) \
        & (rows["tick"] >= 0)
    parts = {}
    for name, mask in (("no_chunk", inside & (rows["chunk_tokens"] == 0)),
                       ("chunk", inside & (rows["chunk_tokens"] > 0))):
        if mask.any():
            parts[name] = {"ticks": int(mask.sum()), **{
                p: statistics.median(rows[p][mask].tolist()) / 1e6
                for p in ticklog.PARTS}}
    snap = registry().snapshot()
    out = {
        "cell": cell, "seed": seed, "correct": line["correct"],
        "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        "holds": rec["holds"],
        "hold_lost_ms": holds.lost_ms(ran),
        "hold_unexplained_pct": holds.unexplained_pct(ran),
        "tokens_per_s_outside_holds": holds.tokens_per_s_outside(ran),
        "tick_ms_p50_in_window": holds.tick_ms_p50(ran),
        "ticks_in_window": int(inside.sum()),
        "waited_for": int((inside & (rows["waited"] == 1)).sum()),
        "starved_dispatches": int((inside & (rows["starved"] == 1)).sum()),
        "part_ms_p50": parts,
        "window_gc_ms": float(rows["gc_ns"][inside].sum() / 1e6),
        "window_runq_ms": float(rows["runq_ns"][inside].clip(0).sum() / 1e6),
        "window_majflt": int(rows["majflt"][inside].clip(0).sum()),
        "window_nivcsw": int(rows["nivcsw"][inside].clip(0).sum()),
        "proc": {k: v["value"] for k, v in snap.items()
                 if k.startswith("proc/gc_") or k.startswith("serving/hold")},
    }
    print(MARK + json.dumps(out), flush=True)
    return 0


def spread(values) -> float:
    """Distance between the quartiles over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--runs", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json"),
                  encoding="utf-8") as f:
            args.seconds = float(json.load(f)["run_seconds"])
    if args.runs == 1:
        return one_run(args.cell, args.seed, args.seconds, args.trace)
    runs = []
    for k in range(args.runs):      # a process each: this one stays off jax
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), args.cell, "--seed",
             str(args.seed + k), "--seconds", str(args.seconds), "--trace",
             str(args.trace)], cwd=ROOT, stdout=subprocess.PIPE, text=True)
        sys.stdout.write(p.stdout)
        sys.stdout.flush()
        if p.returncode:
            return p.returncode
        runs += [json.loads(ln[len(MARK):]) for ln in p.stdout.splitlines()
                 if ln.startswith(MARK + "{")]
    summary = {"cell": args.cell, "runs": len(runs),
               "holds": sum(len(r["holds"]) for r in runs),
               "hold_lost_ms": [r["hold_lost_ms"] for r in runs]}
    for key in ("serve_tokens_per_s", "itl_p95_ms"):
        values = [r["metrics"][key] for r in runs if key in r["metrics"]]
        if len(values) >= 2:
            summary[key] = values
            summary[key + "_spread"] = spread(values)
    outside = [r["tokens_per_s_outside_holds"] for r in runs
               if r["tokens_per_s_outside_holds"] is not None]
    if len(outside) >= 2:
        summary["tokens_per_s_outside_holds"] = outside
        summary["tokens_per_s_outside_holds_spread"] = spread(outside)
    print(MARK + "summary " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
