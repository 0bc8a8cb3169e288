"""What the program itself names in a traced run, read once a process from
the xplane in ``.perfbench_trace``: for every device operation the scope
path the program gave it (``jax.named_scope`` names such as ``blk/attn``,
carried by the HLO ``op_name``), and for the host every ``pt:`` span
(``paddle_tpu.profiler.trace.scope``) with its stats (``tick``,
``waited``). The readers ``tick.*_ms_per_tick``, ``train.*_ms_per_step``,
``flash.fwd/bwd_ms_per_step``, ``tick.handoff_lag_ms_p50`` and
``sched.host_ms_per_tick``/``idle_outside_program_spans_pct`` are a few
lines each on top of this file.

The plain form is ``tracered``'s with two more keys: a device event has
``"scope"`` (the op's scope path, ``""`` where the trace gives none) and a
``pt:`` event has ``"stats"``. So every reduction of ``tracered`` works on
it, and a recorded piece of it is kept with the tests. A program that names
nothing (the parent of the PR that brought the names) gives no scope and no
``pt:`` span: every reader here then returns ``None`` and raises nothing.

Run as a file it prints where the device's idle time began, by the
innermost open ``pt:`` span, and with ``<out.json[.gz]> [<milliseconds>]``
it also records the first milliseconds of the trace in the plain form::

    python3 perfbench/layer_metrics/_program_trace.py [<out.json.gz> [<ms>]]
"""
from __future__ import annotations

import gzip
import json
import os
import re
import statistics
import sys
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):          # run as a file: find the package
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from perfbench import loader, tracered, yardstick  # noqa: E402

#: prefix of the program's own host spans (profiler/trace.py SPAN_PREFIX)
PT = "pt:"
#: the stat of an operation's event metadata that holds the HLO
#: ``op_name``, which is the scope path (seen on the v5e)
SCOPE_STATS = ("tf_op",)
MODULE_LINES = ("XLA Modules",)
#: a scope name of the program: ``blk/attn``, ``tick/head``, ``fwd/stem``,
#: ``opt/update``; inside ``jvp(...)``/``transpose(...)`` wrappers too
_SCOPE = re.compile(r"\b(blk|tick|fwd|opt)/([a-z_]+)")
#: Pallas kernels by the ``name=`` the program gives the call
FLASH_FWD, FLASH_BWD = ("flash_fwd",), ("flash_bwd",)

_DOC: Dict[str, Optional[dict]] = {}


# --- reading ----------------------------------------------------------------
# On the v5e the scope path of an operation is the ``tf_op`` stat of its
# event's *metadata* (``jit(tick)/cond/.../blk/attn/gather:``); the event's
# own stats, which are all ``jax.profiler.ProfileData`` shows, are only its
# device times (looked at by hand, PR 24). So the metadata table of each
# device plane is read from the protobuf's wire format here, a few fields
# of XSpace -> XPlane -> XEventMetadata -> XStat, and joined to the events
# by their name, which is the metadata's name (the whole HLO instruction).
def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _map_entry(buf) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {event name: scope path}}`` from the ``tf_op``
    stat (``SCOPE_STATS``) of each event's metadata."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(space):
        if f != 1:                                   # XSpace.planes
            continue
        name, stat_names, metadata = "", {}, []
        for f2, v in _fields(plane):
            if f2 == 2:                              # XPlane.name
                name = bytes(v).decode()
            elif f2 == 4:                            # .event_metadata
                metadata.append(_map_entry(v)[1])
            elif f2 == 5:                            # .stat_metadata
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode() for g, x in _fields(meta) if g == 2),
                    "")
        if not name.startswith("/device:"):
            continue
        wanted = {k for k, n in stat_names.items() if n in SCOPE_STATS}
        scopes = out.setdefault(name, {})
        for meta in metadata:
            ev_name, scope = "", ""
            for f3, v in _fields(meta):
                if f3 == 2:                          # XEventMetadata.name
                    ev_name = bytes(v).decode()
                elif f3 == 5 and not scope:          # .stats
                    stat = dict(_fields(v))
                    if stat.get(1) in wanted:
                        if 5 in stat:                # str_value
                            scope = bytes(stat[5]).decode()
                        elif 7 in stat:              # ref_value
                            scope = stat_names.get(stat[7], "")
            if scope:
                scopes[ev_name[:tracered.NAME_LIMIT]] = scope
    return out


def read(path: str) -> dict:
    """The plain form of an ``.xplane.pb`` with scopes and ``pt:`` stats."""
    from jax.profiler import ProfileData

    scopes = op_scopes(path)
    doc = {"planes": []}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        scope_of = scopes.get(plane.name, {})
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if not device and not ev.name.startswith(PT):
                    continue
                d = {"name": ev.name[:tracered.NAME_LIMIT],
                     "start_ns": int(ev.start_ns),
                     "dur_ns": int(ev.duration_ns)}
                if device:
                    d["scope"] = scope_of.get(d["name"], "")
                else:
                    d["stats"] = {k: v for k, v in ev.stats
                                  if isinstance(v, (int, float, str))}
                events.append(d)
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            doc["planes"].append({"name": plane.name, "lines": lines})
    return doc


def load() -> Optional[dict]:
    """The traced run's trace, read once a process; ``None`` without one."""
    if "doc" not in _DOC:
        path = tracered.find_xplane(loader.root_file(".perfbench_trace"))
        _DOC["doc"] = read(path) if path else None
    return _DOC["doc"]


def doc_of(run) -> Optional[dict]:
    """For a reader: the trace of a traced run, else ``None``."""
    return load() if run["ctx"].trace_doc is not None else None


# --- device: scopes ---------------------------------------------------------
def scope_name(ev: dict) -> str:
    """The innermost of the program's scope names on an operation's path:
    ``blk/attn`` of ``jit(tick)/.../blk/attn/blk/kv_scatter/scatter`` is
    ``blk/kv_scatter``; ``""`` under none."""
    found = _SCOPE.findall(ev.get("scope", ""))
    return "/".join(found[-1]) if found else ""


def kernel_name(ev: dict) -> str:
    """``flash_bwd_dq`` of ``%flash_bwd_dq.9 = ... custom-call(...)``:
    a Pallas call carries its ``name=`` as its instruction's name."""
    return tracered.short_name(ev).rsplit(".", 1)[0]


def names_parts(doc: dict) -> bool:
    """Whether the traced program names the parts of its block at all."""
    return any(scope_name(ev).startswith("blk/")
               for p in tracered.device_planes(doc)
               for ev in tracered.op_events(p))


def program_runs(plane: dict, word: str) -> List[dict]:
    """The runs of the programs whose name holds ``word`` (``tick``,
    ``step``) on one chip, in order."""
    return sorted((ev for ln in plane["lines"] if ln["name"] in MODULE_LINES
                   for ev in ln["events"] if word in ev["name"].lower()),
                  key=lambda ev: ev["start_ns"])


def whole_runs(runs: List[dict]) -> List[dict]:
    """Without the runs the trace's edges cut: a program that was running
    when the profiler started or stopped is recorded with the part of its
    time that lay inside (seen: 0.0, 0.7, 4.4 and 7.3 ms of a 57 ms
    tick), so a run under half the median is left out."""
    if not runs:
        return []
    half = statistics.median(r["dur_ns"] for r in runs) / 2
    return [r for r in runs if r["dur_ns"] >= half]


def parts_ms(doc: dict, word: str, label_of: Callable[[dict], str],
             order: Sequence[str]) -> Optional[Dict[str, float]]:
    """Device milliseconds of the whole runs of the ``word`` programs by
    part, mean over the chips: ``label_of(ev)`` names an operation's
    part, and where operations overlap the time goes to the part that
    comes first in ``order``. ``"runs"`` is the programs' own time and
    ``"n_runs"`` their count (on one chip); parts and ``"in no
    operation"`` add up to ``"runs"``. A pass is linear in the trace
    (11-14 s on Ling's 4 s, PR 53), so each is counted: ``cuts_of(doc)``
    names the label functions' modules the trace was cut for."""
    planes = tracered.device_planes(doc)
    doc.setdefault("_memo", {}).setdefault("cuts", []).append(
        label_of.__module__.rsplit(".", 1)[-1])
    out: Dict[str, float] = defaultdict(float)
    for p in planes:
        runs = whole_runs(program_runs(p, word))
        if not runs:
            return None
        inside = tracered.merge(tracered.intervals(runs))
        starts = [s for s, _ in inside]
        by_label: Dict[str, list] = defaultdict(list)
        for ev in tracered.op_events(p):
            iv = (ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
            if tracered.overlaps(iv, inside, starts):
                by_label[label_of(ev)].append(iv)
        covered: list = []
        for label in list(order) + sorted(set(by_label) - set(order)):
            ivs = tracered.merge(by_label.get(label, []))
            out[label] += (tracered.union_ns(ivs)
                           - tracered.intersection_ns(ivs, covered)) / 1e6
            covered = tracered.merge(covered + ivs)
        out["runs"] += tracered.union_ns(inside) / 1e6
        out["in no operation"] += (
            tracered.union_ns(inside)
            - tracered.intersection_ns(inside, covered)) / 1e6
        out["n_runs"] += len(runs)
    return {k: v / len(planes) for k, v in out.items()} if planes else None


def cuts_of(doc: dict) -> List[str]:
    """The module of every label function ``parts_ms`` cut ``doc`` for, in
    order: a traced run should cut its trace for its own helper alone."""
    return list(doc.get("_memo", {}).get("cuts", ()))


def names_scope(doc: dict, scope_re, names: Sequence[str]) -> bool:
    """Whether some device operation's innermost name, of those
    ``scope_re`` finds on its scope path, is one of ``names``: what a served
    family's helper asks before it cuts the trace (its tick's own
    mechanism, ``blk/gdn/step``), so that the helpers whose tick this is
    not pay one look at the trace's distinct scope paths, some thousands and
    gathered once a trace, and no pass of ``parts_ms``."""
    scopes = _once(doc, "scopes", lambda: frozenset(
        ev.get("scope", "") for p in tracered.device_planes(doc)
        for ev in tracered.op_events(p)))

    def innermost(scope: str) -> str:
        found = scope_re.findall(scope)
        return found[-1] if found else ""

    return any(innermost(s) in names for s in scopes)


TICK_ORDER = ("kv_scatter", "attn", "dense", "head_sample", "unscoped")
_TICK_PART = {"blk/kv_scatter": "kv_scatter", "blk/attn": "attn",
              "blk/qkv": "dense", "blk/attn_out": "dense",
              "blk/ffn": "dense", "tick/embed": "head_sample",
              "tick/head": "head_sample", "tick/sample": "head_sample"}


def tick_part(ev: dict) -> str:
    return _TICK_PART.get(scope_name(ev), "unscoped")


def _once(doc: dict, key: str, compute: Callable[[], object]):
    """A reduction of ``doc`` kept with it: several metrics read one."""
    memo = doc.setdefault("_memo", {})
    if key not in memo:
        memo[key] = compute()
    return memo[key]


def tick_parts_ms(doc: Optional[dict]) -> Optional[Dict[str, float]]:
    """The serving tick's device time by part, milliseconds a tick."""
    if doc is None:
        return None

    def compute():
        parts = parts_ms(doc, "tick", tick_part, TICK_ORDER) \
            if names_parts(doc) else None
        if not parts:
            return None
        n = parts.pop("n_runs")
        return {k: v / n for k, v in parts.items()}

    return _once(doc, "tick parts", compute)


def read_tick_part(run, part: str) -> Optional[float]:
    """What a ``tick.<part>_ms_per_tick`` metric's ``read`` returns."""
    parts = tick_parts_ms(doc_of(run))
    if parts is None:
        return None
    say_parts(run, "tick parts a tick", parts)
    return parts[part]


STEP_ORDER = ("flash_fwd", "flash_bwd", "dense", "opt", "head", "unscoped")


def step_part(ev: dict) -> str:
    kernel = kernel_name(ev)
    if kernel.startswith(FLASH_BWD):
        return "flash_bwd"
    if kernel.startswith(FLASH_FWD):
        return "flash_fwd"
    name = scope_name(ev)
    if name.startswith("blk/"):
        return "dense"
    return {"opt/update": "opt", "fwd/stem": "head",
            "fwd/head": "head"}.get(name, "unscoped")


def step_parts_ms(doc: Optional[dict],
                  steps: Optional[int]) -> Optional[Dict[str, float]]:
    """The training step's device time by part, milliseconds a step."""
    if doc is None or not steps:
        return None

    def compute():
        parts = parts_ms(doc, "step", step_part, STEP_ORDER) \
            if names_parts(doc) else None
        if not parts:
            return None
        parts.pop("n_runs")
        return {k: v / steps for k, v in parts.items()}

    return _once(doc, f"step parts / {steps}", compute)


def read_step_part(run, part: str) -> Optional[float]:
    """What a ``train.<part>_ms_per_step`` metric's ``read`` returns."""
    parts = step_parts_ms(doc_of(run), run["facts"].get("traced_steps"))
    if parts is None:
        return None
    say_parts(run, "step parts a step", parts)
    return parts[part]


def kernel_ms(doc: Optional[dict], prefixes: Tuple[str, ...],
              steps: Optional[int]) -> Optional[float]:
    """Device milliseconds a step in the Pallas calls whose name starts
    with one of ``prefixes``; ``None`` where the trace names none."""
    if doc is None or not steps:
        return None
    ms = tracered.kernel_s(
        doc, lambda ev: tracered.is_mosaic_call(ev)
        and kernel_name(ev).startswith(prefixes)) * 1e3
    return ms / steps if ms else None


def say_parts(run, title: str, parts: Dict[str, float]) -> None:
    """One note a run, whichever reader comes first."""
    note = title + ": " + ", ".join(
        f"{k} {v:.3f}" for k, v in parts.items()) + " ms"
    if note not in run.setdefault("notes", []):
        run["notes"].append(note)


# --- host: the program's spans ----------------------------------------------
def pt_spans(doc: dict) -> List[dict]:
    """The program's host spans in order of their start:
    ``{"name", "start_ns", "end_ns", "stats"}``, the name without
    ``pt:``."""
    out = []
    for p in doc["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            for ev in ln["events"]:
                if ev["name"].startswith(PT):
                    out.append({"name": ev["name"][len(PT):],
                                "start_ns": ev["start_ns"],
                                "end_ns": ev["start_ns"] + ev["dur_ns"],
                                "stats": ev.get("stats", {})})
    return sorted(out, key=lambda s: s["start_ns"])


def align_ticks(doc: dict) -> Optional[dict]:
    """Lays the engine's tick numbers against the device's runs of the
    tick program. The device runs ticks in the order they were dispatched,
    so tick ``n`` is run number ``n - first + offset`` of the trace, where
    ``first`` is the tick of the first traced ``pt:step/dispatch`` and
    ``offset`` the ticks still in flight when the trace began. Each drain
    that had to wait (``waited=1``) fixes the offset: it ends one transfer
    after its tick does, so the last run that ended before it is its own.

    ``{"runs": [...], "first": n, "offset": k, "anchors": [k, ...],
    "run_of": {tick: run}, "unmatched_runs": m}``, ``m`` counting the
    runs from number ``offset`` on that no traced dispatch answers for
    (the ``offset`` runs before them were dispatched before the trace
    began, whenever they ran); ``None`` without tick runs or dispatch
    spans."""
    planes = tracered.device_planes(doc)
    runs = program_runs(planes[0], "tick") if planes else []
    spans = pt_spans(doc)
    dispatched = [s for s in spans if s["name"] == "step/dispatch"]
    if not runs or not dispatched:
        return None
    first = int(dispatched[0]["stats"]["tick"])
    ends = [r["start_ns"] + r["dur_ns"] for r in runs]
    anchors = []
    for s in spans:
        if s["name"] != "step/drain" or not int(s["stats"].get("waited", 0)):
            continue
        done = sum(e <= s["end_ns"] for e in ends)      # runs ended by then
        if done and int(s["stats"]["tick"]) >= first:
            anchors.append(done - 1 - (int(s["stats"]["tick"]) - first))
    offset = max(set(anchors), key=anchors.count) if anchors else 0
    run_of = {}
    for s in dispatched:
        i = int(s["stats"]["tick"]) - first + offset
        if 0 <= i < len(runs):
            run_of[int(s["stats"]["tick"])] = runs[i]
    return {"runs": runs, "first": first, "offset": offset,
            "anchors": anchors, "run_of": run_of,
            "unmatched_runs": len(runs) - max(offset, 0) - len(run_of)}


def handoff_lags_ms(doc: Optional[dict]) -> Optional[List[float]]:
    """For every drained tick: from the end of its run on the device to
    the end of the ``pt:step/drain`` that brought its tokens to the
    host."""
    al = align_ticks(doc) if doc is not None else None
    if al is None:
        return None
    lags = []
    for s in pt_spans(doc):
        run = al["run_of"].get(int(s["stats"].get("tick", -1))) \
            if s["name"] == "step/drain" else None
        if run is not None:
            lags.append((s["end_ns"] - run["start_ns"] - run["dur_ns"])
                        / 1e6)
    return lags or None


#: what the host itself spends on a tick; a drain that waited is the
#: device's time, not the host's
HOST_PHASES = ("step/admit", "step/chunks", "step/grow", "step/build",
               "step/dispatch")


def host_ms_per_tick(doc: Optional[dict]) -> Optional[float]:
    spans = pt_spans(doc) if doc is not None else []
    ticks = sum(s["name"] == "step/dispatch" for s in spans)
    if not ticks:
        return None
    mine = [s for s in spans if s["name"] in HOST_PHASES or (
        s["name"] == "step/drain" and not int(s["stats"].get("waited", 0)))]
    return sum(s["end_ns"] - s["start_ns"] for s in mine) / 1e6 / ticks


NO_SPAN = "(no pt: span)"


def idle_by_pt_span(doc: dict) -> Dict[str, float]:
    """Seconds of device idleness inside the traced window by the
    innermost ``pt:`` span open when each gap began, mean over the
    chips: ``tracered.idle_gaps_by_span`` with the program's spans in
    the place of the benchmark's."""
    as_pb = [{"name": tracered.SPAN_PREFIX + s["name"],
              "start_ns": s["start_ns"],
              "dur_ns": s["end_ns"] - s["start_ns"]} for s in pt_spans(doc)]
    idle = tracered.idle_gaps_by_span({"planes": tracered.device_planes(doc)
                                       + [{"name": "/host:pt", "lines": [
                                           {"name": "pt", "events": as_pb}]}]})
    if "unattributed" in idle:
        idle[NO_SPAN] = idle.pop("unattributed")
    return idle


def idle_outside_pct(doc: Optional[dict]) -> Optional[float]:
    """Of the device's idle time in the traced window, the share that
    began under none of the program's spans; ``None`` where the program
    writes no span."""
    if doc is None or not pt_spans(doc):
        return None
    idle = idle_by_pt_span(doc)
    total = sum(idle.values())
    return 100.0 * idle.get(NO_SPAN, 0.0) / total if total else None


def idle_table(doc: dict) -> str:
    idle = sorted(idle_by_pt_span(doc).items(), key=lambda kv: -kv[1])
    return "idle seconds by innermost open pt: span: " + ", ".join(
        f"{k} {v:.6f}" for k, v in idle)


def alignment_note(doc: dict) -> str:
    al = align_ticks(doc)
    if al is None:
        return "no tick runs or no pt:step/dispatch span in the trace"
    agree = sum(a == al["offset"] for a in al["anchors"])
    return (f"{len(al['runs'])} tick runs: {al['offset']} in flight when "
            f"the trace began, {len(al['run_of'])} matched one to one to "
            f"a pt:step/dispatch from tick {al['first']} on, "
            f"{al['unmatched_runs']} unmatched; {len(al['anchors'])} "
            f"anchors (drains that waited), {agree} agree on the offset")


# --- a recorded piece, for the tests ----------------------------------------
def trimmed(doc: dict, span_ns: int, name_limit: int = 120,
            scope_limit: int = 160) -> dict:
    """The events that end within ``span_ns`` of the first device event
    (a program run that is cut there is left out whole, so the runs kept
    have all their operations): names cut to ``name_limit`` characters,
    scopes to their last ``scope_limit``; device lines other than the
    programs' and the operations' are left out, and the control-flow
    operations that span their children (``tracered.op_events`` leaves
    them out too, but knows them by an opcode the cut would lose)."""
    win = tracered.window_of(doc)
    hi = win[0] + span_ns
    keep = MODULE_LINES + ("XLA Ops",)
    planes = []
    for p in doc["planes"]:
        lines = []
        for ln in p["lines"]:
            if p["name"].startswith("/device:") and ln["name"] not in keep:
                continue
            evs = []
            for e in ln["events"]:
                if e["start_ns"] + e["dur_ns"] > hi or (
                        "scope" in e and tracered.opcode(e)
                        in tracered._CONTAINERS):
                    continue     # a while or cond, known by its whole name
                e = dict(e, name=e["name"][:name_limit])
                if "scope" in e:
                    e["scope"] = e["scope"][-scope_limit:]
                evs.append(e)
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


def load_recorded(path: str) -> dict:
    """A recorded piece of a trace: ``.json``, or ``.json.gz`` (a tick is
    some 2,000 operations, each with its instruction and its scope)."""
    with (gzip.open(path, "rt") if path.endswith(".gz")
          else open(path)) as f:
        return json.load(f)


if __name__ == "__main__":
    the_doc = load()
    if the_doc is None:
        raise SystemExit("no trace in .perfbench_trace: make a --trace 1 "
                         "run first")
    print(idle_table(the_doc))
    print(alignment_note(the_doc))
    lags = handoff_lags_ms(the_doc)
    if lags:
        print("handoff lag ms p50 / p95 / max: "
              + " / ".join(f"{x:.3f}" for x in (
                  yardstick.percentile(lags, 50),
                  yardstick.percentile(lags, 95), max(lags))))
    if len(sys.argv) > 1:
        ms = float(sys.argv[2]) if len(sys.argv) > 2 else 150.0
        with (gzip.open(sys.argv[1], "wt") if sys.argv[1].endswith(".gz")
              else open(sys.argv[1], "w")) as f:
            json.dump(trimmed(the_doc, int(ms * 1e6)), f)
