"""From a profiler trace to numbers. The reductions work on a plain form of
the trace (dicts and lists, as ``read_xplane`` gives it and as the recorded
trace in tests/perfbench is stored), so they are tested without a chip.

Plain form: ``{"planes": [{"name": str, "lines": [{"name": str, "events":
[{"name": str, "start_ns": int, "dur_ns": int}]}]}]}``. On the TPU an
operation's event is named by its whole HLO instruction, ``%copy.117 =
bf16[24,1537,16,16,128]{...} copy(...)``: short name, result shape and
opcode are read from that text (PR 23 looked at one by hand).
The interval arithmetic is copied from paddle_tpu/profiler/device_trace.py
(``_merge``, ``interval_union_ms``, ``_intersection_len_us``,
``collective_kind``), which stays with the program.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

#: an event's name is cut to this in the plain form: result shape and
#: opcode come first, the operands after them are of no use here
NAME_LIMIT = 4096
#: lines of a device plane that hold one event per operation run
_OP_LINES = ("XLA Ops",)
#: prefix of the host spans the benchmark writes (jax TraceAnnotation)
SPAN_PREFIX = "pb:"

_COLLECTIVES = ("all-reduce", "all_reduce", "all-gather", "all_gather",
                "reduce-scatter", "reduce_scatter", "all-to-all",
                "all_to_all", "collective-permute", "collective_permute",
                "collective-broadcast", "collective_broadcast")
_SHAPE = re.compile(r"\b([a-z]+[0-9]+[a-z0-9]*|pred)\[([0-9, ]*)\]")
_OPCODE = re.compile(r"[\)\}\]] ([a-z][a-z\-]*)\(")
_CONTAINERS = ("while", "conditional", "call")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'


# --- reading ----------------------------------------------------------------
def find_xplane(log_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def read_xplane(path: str, keep_host: Sequence[str] = (SPAN_PREFIX,)) -> dict:
    """The plain form of an ``.xplane.pb``: every event of the device
    planes, and of the host planes the events whose name starts with one
    of ``keep_host`` (the benchmark's own spans)."""
    from jax.profiler import ProfileData

    doc = {"planes": []}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if not device and not ev.name.startswith(tuple(keep_host)):
                    continue
                events.append({"name": ev.name[:NAME_LIMIT],
                               "start_ns": int(ev.start_ns),
                               "dur_ns": int(ev.duration_ns)})
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            doc["planes"].append({"name": plane.name, "lines": lines})
    return doc


# --- selection --------------------------------------------------------------
def device_planes(doc: dict) -> List[dict]:
    """The planes of the chips (``/device:TPU:<n>``), not their extra
    planes for sparse cores or host offload."""
    return [p for p in doc["planes"]
            if re.fullmatch(r"/device:TPU:\d+", p["name"])]


def op_events(plane: dict) -> List[dict]:
    """One event per operation run on the plane's chip. A control-flow
    operation (``while``, ``conditional``) spans the operations inside
    it; it is left out where the line holds its children too."""
    events = [ev for ln in plane["lines"] if ln["name"] in _OP_LINES
              for ev in ln["events"]]
    return [ev for ev in events if not _is_container(ev)]


def short_name(ev: dict) -> str:
    """``copy.117`` of ``%copy.117 = bf16[...] copy(...)``."""
    return ev["name"].split(" = ", 1)[0].lstrip("%")


def opcode(ev: dict) -> str:
    """``copy`` of ``%copy.117 = bf16[...] copy(...)``; for a name that
    is no HLO instruction, the name up to its first dot."""
    m = _OPCODE.search(ev["name"]) if " = " in ev["name"] else None
    return m.group(1) if m else short_name(ev).split(".")[0]


def _is_container(ev: dict) -> bool:
    return opcode(ev) in _CONTAINERS


def host_spans(doc: dict) -> List[Tuple[str, int, int]]:
    """``(name, start_ns, end_ns)`` of the benchmark's own host spans."""
    out = []
    for p in doc["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            for ev in ln["events"]:
                if ev["name"].startswith(SPAN_PREFIX):
                    out.append((ev["name"][len(SPAN_PREFIX):],
                                ev["start_ns"],
                                ev["start_ns"] + ev["dur_ns"]))
    return sorted(out, key=lambda s: s[1])


def intervals(events: Iterable[dict]) -> List[Interval]:
    return [(ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
            for ev in events if ev["dur_ns"] > 0]


# --- interval arithmetic ----------------------------------------------------
def merge(ivs: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(ivs: Sequence[Interval]) -> int:
    return sum(e - s for s, e in merge(ivs))


def intersection_ns(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    a, b = merge(a), merge(b)
    total = i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def overlaps(iv: Interval, merged: Sequence[Interval],
             starts: Sequence[int]) -> bool:
    """``intersection_ns([iv], merged) > 0`` for ``merged`` as ``merge``
    gives it from intervals of some length (``intervals`` keeps no other)
    and ``starts`` its intervals' starts, by bisection and not by a
    walk: a traced stretch holds some hundreds of program runs and a million
    operations, and asking each operation against every run took minutes
    (three such passes made Ling's traced run 1,015 s long, PR 53)."""
    lo, hi = iv
    i = bisect.bisect_right(starts, lo) - 1
    if i >= 0 and merged[i][1] > lo:
        return hi > lo
    return i + 1 < len(merged) and merged[i + 1][0] < hi


def clip(ivs: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in ivs
            if min(e, hi) > max(s, lo)]


# --- reductions -------------------------------------------------------------
def window_of(doc: dict) -> Optional[Interval]:
    """The traced window: from the first to the last device operation on
    any chip."""
    ivs = [iv for p in device_planes(doc) for iv in intervals(op_events(p))]
    if not ivs:
        return None
    return min(s for s, _ in ivs), max(e for _, e in ivs)


def busy_s(doc: dict) -> float:
    """Seconds in which an operation ran on the device: the union of the
    operations' intervals on each chip, averaged over the chips."""
    planes = device_planes(doc)
    if not planes:
        return 0.0
    return sum(union_ns(intervals(op_events(p))) for p in planes) \
        / len(planes) / 1e9


def idle_gaps_by_span(doc: dict) -> Dict[str, float]:
    """Seconds of device idleness inside the window, by what the host was
    doing when each gap began: the innermost of the benchmark's spans that
    was open then, or ``unattributed``. Averaged over the chips."""
    win = window_of(doc)
    planes = device_planes(doc)
    if win is None:
        return {}
    spans = host_spans(doc)
    out: Dict[str, float] = defaultdict(float)
    for p in planes:
        busy = merge(clip(intervals(op_events(p)), *win))
        edges = [win[0]] + [x for s, e in busy for x in (s, e)] + [win[1]]
        for gap_start, gap_end in zip(edges[0::2], edges[1::2]):
            if gap_end <= gap_start:
                continue
            open_then = [s for s in spans if s[1] <= gap_start < s[2]]
            name = max(open_then, key=lambda s: s[1])[0] if open_then \
                else "unattributed"
            out[name] += (gap_end - gap_start) / 1e9 / len(planes)
    return dict(out)


def result_shape(ev: dict) -> Optional[Tuple[str, Tuple[int, ...]]]:
    """``(dtype, dims)`` of what the operation produces: the first shape
    after the ``=`` of its HLO text. A tuple result gives its first
    member."""
    if " = " not in ev["name"]:
        return None
    m = _SHAPE.search(ev["name"].split(" = ", 1)[1])
    if m is None:
        return None
    dims = tuple(int(d) for d in m.group(2).replace(" ", "").split(",") if d)
    return m.group(1), dims


def op_label(ev: dict) -> str:
    """The operation's name with its result shape, as one token."""
    shape = result_shape(ev)
    if shape is None:
        return short_name(ev)
    return f"{short_name(ev)}_{shape[0]}_" + \
        "_".join(map(str, shape[1])) + "_"


def top_ops(doc: dict, n: int = 10) -> List[List]:
    """``[[label, seconds], ...]``: the operations that took most device
    time, summed over their runs and averaged over the chips."""
    planes = device_planes(doc)
    total: Dict[str, float] = defaultdict(float)
    for p in planes:
        for ev in op_events(p):
            total[op_label(ev)] += ev["dur_ns"] / 1e9 / len(planes)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def collective_kind(ev: dict) -> Optional[str]:
    """The kind of collective an operation is, by its own name and opcode
    (never by its operands, which may name a collective's result)."""
    n = (short_name(ev) + " " + opcode(ev)).lower()
    for pat in _COLLECTIVES:
        if pat in n:
            return pat.replace("_", "-")
    return None


def is_mosaic_call(ev: dict) -> bool:
    """A Pallas kernel: a custom call whose target is Mosaic's."""
    return opcode(ev) == "custom-call" and MOSAIC_TARGET in ev["name"]


def exposed_collective_s(doc: dict) -> float:
    """Seconds in which a collective ran on a chip and no other operation
    did on that chip: collective time minus its overlap with compute.
    Averaged over the chips."""
    planes = device_planes(doc)
    if not planes:
        return 0.0
    total = 0
    for p in planes:
        events = op_events(p)
        coll = intervals(ev for ev in events if collective_kind(ev))
        comp = intervals(ev for ev in events if not collective_kind(ev))
        total += union_ns(coll) - intersection_ns(coll, comp)
    return total / 1e9 / len(planes)


def kernel_s(doc: dict, is_kernel) -> float:
    """Device seconds of the events ``is_kernel(ev)`` accepts, averaged
    over the chips."""
    planes = device_planes(doc)
    if not planes:
        return 0.0
    return sum(ev["dur_ns"] for p in planes for ev in op_events(p)
               if is_kernel(ev)) / 1e9 / len(planes)
