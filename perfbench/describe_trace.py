"""Look at a trace by hand before writing code against it:

    python3 perfbench/describe_trace.py <out-dir> [<milliseconds>]

reads the trace the last ``--trace 1`` run left in ``.perfbench_trace`` and
writes ``describe.json`` (planes, lines, event counts, a few events with
every stat) and ``recorded.json`` (the plain form of the first
milliseconds, small enough to keep with the tests).
"""
import json
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from perfbench import loader, tracered  # noqa: E402


def every_stat(path: str, examples: int = 3) -> dict:
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {"events": len(evs), "first": [
                {"name": e.name, "start_ns": int(e.start_ns),
                 "dur_ns": int(e.duration_ns),
                 "stats": {k: str(v)[:300] for k, v in e.stats}}
                for e in evs[:examples]]}
        out[plane.name] = lines
    return out


def trimmed(doc: dict, span_ns: int, name_limit: int = 240) -> dict:
    """The events of the first ``span_ns`` after the first device event,
    their names cut to ``name_limit`` characters."""
    win = tracered.window_of(doc)
    if win is None:
        return doc
    lo, hi = win[0], win[0] + span_ns
    planes = []
    for p in doc["planes"]:
        lines = []
        for ln in p["lines"]:
            evs = [dict(e, name=e["name"][:name_limit]) for e in ln["events"]
                   if e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo]
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


if __name__ == "__main__":
    src = tracered.find_xplane(loader.root_file(".perfbench_trace"))
    if src is None:
        raise SystemExit("no trace in .perfbench_trace: make a --trace 1 "
                         "run first")
    dst = sys.argv[1]
    os.makedirs(dst, exist_ok=True)
    with open(os.path.join(dst, "describe.json"), "w") as f:
        json.dump(every_stat(src), f, indent=1)
    span = int(float(sys.argv[2]) * 1e6) if len(sys.argv) > 2 else 150_000_000
    with open(os.path.join(dst, "recorded.json"), "w") as f:
        json.dump(trimmed(tracered.read_xplane(src), span), f)
