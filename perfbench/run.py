"""One run of one cell:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. Needs the TPU the cell asks for and exits non-zero without
it. The last line of standard output is the result, one JSON object;
everything before it is for a reader.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

if __package__ in (None, ""):          # run as a file: find the package
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from perfbench import harness, loader  # noqa: E402


def say(msg: str) -> None:
    print(f"[perfbench] {msg}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = loader.load_cell(args.workload)
    cache = harness.enable_compile_cache()
    devices = harness.require_tpu(cell["cell"]["chips"])
    family = loader.load_module("families", cell["config"]["family"])
    say(f"{args.workload}: {len(devices)} x {devices[0].device_kind}, "
        f"seed {args.seed}, {args.seconds:g} s, trace {args.trace}, "
        f"compile cache {cache}")

    ctx = harness.Context(cell, args.seed, args.seconds, bool(args.trace),
                          devices)
    run = family.run(ctx)
    ctx.read_trace()
    run["ctx"] = ctx

    metrics = {}
    if args.trace:
        for m in cell["per_layer"]:
            value = loader.load_module("layer_metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = dict(run["end_to_end"], setup_s=ctx.setup_s)
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    for note in run.get("notes", []):
        say(note)
    line = {"correct": bool(run["correct"]),
            "attempted": int(run["attempted"]), "failed": int(run["failed"]),
            "metrics": metrics,
            "device": harness.device_report(devices, ctx.trace_doc)}
    if ctx.trace_doc is not None:
        line["breakdown"] = harness.breakdown(ctx.trace_doc)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
