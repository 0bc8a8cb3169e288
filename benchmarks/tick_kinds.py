"""A traced served cell's ticks by kind, with a chunk and without one:

    python3 benchmarks/tick_kinds.py serve-falcon-h1-gen-backlog falcon_h1 \
        --seed n [--out chiprun_out/kinds.json]
    python3 benchmarks/tick_kinds.py serve-olmo-hybrid-gen-backlog olmoh --seed n

runs the cell once with ``--trace 1`` in this process, as
``benchmarks/holds.py`` does (``perfbench.run.main`` unchanged), and then lays
two records of that run side by side. The engine's tick log
(``profiler.tick_logs()``: a row a dispatched tick, its ``chunk_tokens``
written where the engine hands the tick ``has_chunks``) says each tick's
kind; the benchmark's own readers say what the device did in it:
``_program_trace.align_ticks`` finds the device's run of each tick number,
and ``_program_trace.parts_ms`` cuts the runs of one kind into the parts that
the cell's ``*_ms_per_tick`` entries cut the mean tick into
(``_<helper>_trace.py``'s ``part`` and ``ORDER``). It prints, for each kind,
how many traced ticks there were, their device time (median and mean) and
every part's milliseconds a tick. It adds up no metric; the metrics are the
benchmark's (PR 55 read the dense part of both kinds of tick this way).

Needs the cell's chip, as ``perfbench/run.py`` does.
"""
import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KINDS = (("with a chunk", True), ("without a chunk", False))


def carried_a_chunk(rows) -> dict:
    """``{tick: bool}`` of a tick log's ``rows()``."""
    return {int(t): int(c) > 0
            for t, c in zip(rows["tick"], rows["chunk_tokens"]) if t >= 0}


def only(doc: dict, runs, module_lines) -> dict:
    """``doc`` with no program's runs but ``runs`` (its own event dicts)."""
    keep = {id(r) for r in runs}
    return {"planes": [
        dict(p, lines=[
            dict(ln, events=[ev for ev in ln["events"] if id(ev) in keep])
            if ln["name"] in module_lines else ln for ln in p["lines"]])
        if p["name"].startswith("/device:") else p for p in doc["planes"]]}


def by_kind(pt, doc: dict, chunked: dict, part, order) -> dict:
    """``{kind: {"n", "share", "tick_p50", "tick_mean", "parts": {part: ms a
    tick}}}`` over the traced ticks the log knows, ``pt`` the module
    ``_program_trace``."""
    al = pt.align_ticks(doc)
    if al is None:
        raise SystemExit("no tick runs or no pt:step/dispatch in the trace")
    known = {t: r for t, r in al["run_of"].items() if t in chunked}
    table = {}
    for kind, want in KINDS:
        runs = pt.whole_runs([r for t, r in known.items()
                              if chunked[t] is want])
        parts = runs and pt.parts_ms(only(doc, runs, pt.MODULE_LINES),
                                     "tick", part, order)
        if not parts:
            continue
        n = parts.pop("n_runs")
        whole = parts.pop("runs")
        ms = [r["dur_ns"] / 1e6 for r in runs]
        table[kind] = {
            "n": int(n), "share": n / len(known),
            "tick_p50": statistics.median(ms), "tick_mean": whole / n,
            "parts": {k: v / n for k, v in sorted(
                parts.items(), key=lambda kv: -kv[1])}}
    return table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("cell")
    ap.add_argument("helper", help="falcon_h1, olmoh, ling3, ...: the "
                    "cell's perfbench/layer_metrics/_<helper>_trace.py")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json"),
                  encoding="utf-8") as f:
            args.seconds = float(json.load(f)["run_seconds"])
    from perfbench import loader, run

    rc = run.main(["--workload", args.cell, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"])
    if rc:
        return rc
    from paddle_tpu.profiler import tick_logs

    pt = loader.load_module("layer_metrics", "_program_trace")
    ot = loader.load_module("layer_metrics", f"_{args.helper}_trace")
    doc = pt.load()
    if doc is None:
        raise SystemExit("the run left no trace in .perfbench_trace")
    log = max(tick_logs().values(), key=lambda lg: lg.total)   # the run's
    chunked = carried_a_chunk(log.rows())
    table = by_kind(pt, doc, chunked, ot.part, ot.ORDER)
    print("[kinds]", pt.alignment_note(doc))
    print(f"[kinds] the log: {len(chunked)} ticks, "
          f"{sum(chunked.values())} with a chunk")
    for kind, r in table.items():
        print(f"[kinds] {kind}: {r['n']} ticks ({100 * r['share']:.1f} %), "
              f"tick {r['tick_p50']:.3f} ms p50, {r['tick_mean']:.3f} mean")
        for k, v in r["parts"].items():
            print(f"[kinds]   {v:9.3f} ms  {k}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
