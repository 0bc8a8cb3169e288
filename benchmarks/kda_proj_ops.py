"""The operations of a traced Solar-Open2 step under one of the
linear-attention layer's parts, by device time:

    python3 perfbench/run.py --workload train-solar-open2-1chip ... --trace 1
    python3 benchmarks/kda_proj_ops.py [part [rows [out.json]]]

reads the trace that run left in ``.perfbench_trace`` through the benchmark's
own readers (``perfbench/layer_metrics/_program_trace.py`` for events and
scopes, ``_kda_trace.kda_part`` for the part: ``proj`` unless named, ``scan``,
``out``, ``shared``) and prints the operations of the whole traced steps,
summed by instruction name without its number (``multiply_add_fusion`` of
``%multiply_add_fusion.12``) and the result's type, milliseconds a step and how
many ran: which fusions a part is made of, and which float32 ``[.., 8192,
8192]`` results are among them. It adds up no metric; the metrics are the
benchmark's. A part of any trainer's step as ``train.*_ms_per_step`` cuts it
(``head``, ``unscoped``, ``dense``, ``opt``, ``flash_fwd``, ``flash_bwd``:
``_program_trace.step_part``) is listed the same way after a ``--trace 1``
run of any training cell, each operation with the innermost scope it lies
under; on several chips the times are the mean of a chip's step. A part of
Olmo-Hybrid's served tick as ``perfbench/layer_metrics/_olmoh_trace.py`` cuts
it (``gdn_prep``, ``gdn_step``, ``gdn_chunk``, ``attn``, ``head_sample``) is
listed after a ``--trace 1`` run of ``serve-olmo-hybrid-gen-backlog``,
milliseconds a tick, each operation with the end of its scope path (PR 46
read ``gdn_prep`` that way before writing ``ops/gdn.py``'s pass); another
served cell's tick by its own helper's parts, the helper named before a
colon (``ling3:experts``, ``dots3:experts``, ``dsv2:experts``: PR 50 listed
what lies around the grouped products that way).
"""
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import loader, tracered                          # noqa: E402


def result_type(instruction: str) -> str:
    """``(bf16[1,8192,8192], f32[4,8192])`` of ``%x.1 = (bf16[1,8192,8192]{2,
    1,0}, f32[4,8192]{1,0}) custom-call(...)``: the result's type without
    its layouts, a tuple whole (as far as the event's name holds it)."""
    rhs = re.sub(r"\{[^}]*\}", "", instruction.split(" = ", 1)[-1])
    if rhs.startswith("("):
        return rhs[:rhs.find(")") + 1 or None][:120]
    return rhs.split(" ", 1)[0]


def main():
    part = sys.argv[1] if len(sys.argv) > 1 else "proj"
    rows = int(sys.argv[2]) if len(sys.argv) > 2 else 40
    pt = loader.load_module("layer_metrics", "_program_trace")
    kt = loader.load_module("layer_metrics", "_kda_trace")
    doc = pt.load()
    if doc is None:
        raise SystemExit("no trace in .perfbench_trace: run a cell with "
                         "--trace 1 first")
    helper, _, part = part.rpartition(":")
    ot = loader.load_module("layer_metrics", f"_{helper or 'olmoh'}_trace")
    word = "tick" if any(pt.program_runs(p, "tick")
                         for p in tracered.device_planes(doc)) else "step"
    part_of, title = (ot.part, part) if word == "tick" \
        else (pt.step_part, part) if part in pt.STEP_ORDER \
        else (kt.kda_part, f"blk/kda/{part}")
    by_name = defaultdict(lambda: {"ms": 0.0, "n": 0})
    steps = 0
    for plane in tracered.device_planes(doc):
        runs = pt.whole_runs(pt.program_runs(plane, word))
        steps += len(runs)
        inside = tracered.merge(tracered.intervals(runs))
        for ev in tracered.op_events(plane):
            iv = (ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
            if part_of(ev) != part \
                    or not tracered.intersection_ns([iv], inside):
                continue
            result = result_type(ev["name"])
            name = re.sub(r"\.\d+$", "", tracered.short_name(ev))
            if part_of is pt.step_part:
                name = f"{name} [{pt.scope_name(ev)}]"
            elif word == "tick":
                name = f"{name} [{'/'.join(ev.get('scope', '').split('/')[-3:])}]"
            rec = by_name[name, result]
            rec["ms"] += ev["dur_ns"] / 1e6
            rec["n"] += 1
    if not steps:
        raise SystemExit("the trace holds no whole step")
    table = sorted(({"name": name, "ms_per_step": v["ms"] / steps,
                     "runs_per_step": v["n"] / steps, "result": result}
                    for (name, result), v in by_name.items()),
                   key=lambda r: -r["ms_per_step"])
    total = sum(r["ms_per_step"] for r in table)
    wide = sum(r["ms_per_step"] for r in table
               if re.search(r"f32\[(\d+,)?8192,8192\]", r["result"]))
    print(f"{title}: {total:.2f} ms a step over {steps} traced "
          f"step(s), {len(table)} kinds of operation; {wide:.2f} ms in "
          "operations whose result is float32 [.., 8192, 8192]")
    for r in table[:rows]:
        print(f"  {r['ms_per_step']:9.3f} ms  x{r['runs_per_step']:6.1f}  "
              f"{r['name'][:56]:56s} {r['result']}")
    if len(sys.argv) > 3:
        with open(sys.argv[3], "w") as f:
            json.dump({"part": part, "steps": steps, "total_ms": total,
                       "wide_f32_ms": wide, "operations": table}, f, indent=1)


if __name__ == "__main__":
    main()
