"""Shared by the ``setup.*`` readers: where ``setup_s`` went, from the
program's own record of its set-up (``paddle_tpu/profiler/trace.py``
``phase`` and ``charge_setup``, ``recompile.py``'s compile listener).

The record: events of kind ``phase`` in the program's always-on event log
(``name``, ``t0_ns``, ``t1_ns``, ``id``, ``parent``, on ``perf_counter_ns``,
the clock ``ctx.t_open`` is on), events of kind ``compile`` (one a program
compiled or fetched: ``site`` and its seconds), the counters
``setup/weights_s{...}`` and ``setup/cast_s{...}`` (the last label is the
phase that was open) and the gauge ``proc/age_at_import_s``.

Five rows partition the host's time before the window, **a second to the
innermost**: before the program, the package's import, the weights, the
build (``setup/trainer``, ``setup/engine`` and their children) and the
first calls. A phase's own time is its length less its children's, whatever
rows they are of, so a first call inside an engine's constructor is the
first calls' and not the build's; weights drawn inside a phase (under
``LazyGuard`` inside ``setup/engine/decode_state``) are the weights' and are
taken out of that phase's row. What is left of ``setup_s`` after the rows
and the traffic's ``warm_in_s`` is ``setup.unaccounted_s``: the harness's
plan, the warm steps' or the warm-up's execution, any hold of the machine.

The compile seconds cut across the rows: they lie inside the first calls
and, for the site ``eager``, inside the weights and the build.

A program without the record (the parent of the PR that brought it) reads
None everywhere and the line leaves the metrics out. So does a rehearsal on
the CPU backend: these are seconds of the chip's host or nothing.
"""
import re

ROWS = ("import", "build", "first_calls")
_LABEL = re.compile(r"phase=([^,}]+)")


def row_of(name: str):
    """The row a phase of this name belongs to, or None."""
    if name == "setup/import":
        return "import"
    if name == "setup/first_call":
        return "first_calls"
    if name.startswith(("setup/trainer", "setup/engine")):
        return "build"
    return None


def record(run):
    """``(phases, compiles, counters)`` as they stood when the window
    opened: the attributes of the ``phase`` events that had ended, those
    of the ``compile`` events, and the registry's snapshot (counters are
    read now: the weights are drawn before the window). None where the
    program keeps no such record, or off the chip."""
    ctx = run["ctx"]
    if ctx.t_open is None or ctx.devices[0].platform != "tpu":
        return None
    try:
        from paddle_tpu.profiler import events, registry
    except ImportError:
        return None
    snap = {k: v.get("value") for k, v in registry().snapshot().items()}
    if snap.get("proc/age_at_import_s") is None:
        return None
    open_ns = ctx.t_open * 1e9
    phases = [e.attrs for e in events.log().events(kind="phase")
              if e.attrs["t1_ns"] <= open_ns]
    compiles = [e.attrs for e in events.log().events(kind="compile")
                if e.t_ns <= open_ns]
    return phases, compiles, snap


def charged(snap: dict) -> list:
    """``(seconds, phase name or None)`` of every ``setup/weights_s`` and
    ``setup/cast_s`` counter."""
    out = []
    for name, value in snap.items():
        if name.startswith(("setup/weights_s", "setup/cast_s")) and value:
            m = _LABEL.search(name)
            out.append((value, m.group(1) if m else None))
    return out


def rows(run):
    """The five disjoint rows, in seconds, or None."""
    rec = record(run)
    if rec is None:
        return None
    phases, _, snap = rec
    out = {r: 0.0 for r in ROWS}
    children = {}
    for p in phases:
        children[p["parent"]] = children.get(p["parent"], 0.0) \
            + (p["t1_ns"] - p["t0_ns"]) / 1e9
    for p in phases:
        row = row_of(p["name"])
        if row is not None:
            out[row] += (p["t1_ns"] - p["t0_ns"]) / 1e9 \
                - children.get(p["id"], 0.0)
    out["weights"] = 0.0
    for seconds, inside in charged(snap):
        out["weights"] += seconds
        row = row_of(inside) if inside else None
        if row is not None:
            out[row] -= seconds
    out["before_program"] = snap["proc/age_at_import_s"]
    return out


def row(run, name: str):
    """One of the five rows, or None."""
    out = rows(run)
    return None if out is None else out[name]


def compile_total(run, key: str):
    """The counter ``compile/<key>`` as it stood when the window opened,
    rebuilt from the ``compile`` events (the registry keeps no history);
    ``programs`` counts them."""
    rec = record(run)
    if rec is None:
        return None
    if key == "programs":
        return float(len(rec[1]))
    return float(sum(c[key] for c in rec[1]))
