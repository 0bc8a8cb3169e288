"""The reader of what the program names in a traced run, on a recorded piece
of one.

``recorded_scoped_tick.json.gz`` holds 125 ms of the traced stretch of the
backlog cell as the TPU v5e's profiler wrote it (PR 24): two whole runs of
the 1.3B tick's program with every operation, each with its scope path (the
``tf_op`` of its event's metadata), and the engine's ``pt:`` spans with
their stats; made on the chip by ``_program_trace.py <out> 125``.
"""
import os
import types

import pytest

from perfbench import loader, tracered

HERE = os.path.dirname(os.path.abspath(__file__))
PT = loader.load_module("layer_metrics", "_program_trace")
TICK_METRICS = {"tick.kv_scatter_ms_per_tick": "kv_scatter",
                "tick.attn_ms_per_tick": "attn",
                "tick.dense_ms_per_tick": "dense",
                "tick.head_sample_ms_per_tick": "head_sample",
                "tick.unscoped_ms_per_tick": "unscoped"}
NEW_METRICS = tuple(TICK_METRICS) + (
    "tick.handoff_lag_ms_p50", "sched.host_ms_per_tick",
    "sched.idle_outside_program_spans_pct", "flash.fwd_ms_per_step",
    "flash.bwd_ms_per_step", "train.dense_ms_per_step",
    "train.head_ms_per_step", "train.opt_ms_per_step",
    "train.unscoped_ms_per_step")


@pytest.fixture(scope="module")
def doc():
    return PT.load_recorded(os.path.join(HERE,
                                         "recorded_scoped_tick.json.gz"))


def traced_run(doc, monkeypatch, **facts):
    """A run as a reader sees it, its trace being ``doc``."""
    monkeypatch.setitem(PT._DOC, "doc", doc)
    return {"ctx": types.SimpleNamespace(trace_doc=doc), "facts": facts}


def test_the_recorded_piece_is_what_it_says(doc):
    (plane,) = tracered.device_planes(doc)
    runs = PT.program_runs(plane, "tick")
    assert len(runs) == 2
    assert all(57.4e6 < r["dur_ns"] < 57.5e6 for r in runs)
    scoped = [ev for ev in tracered.op_events(plane) if ev["scope"]]
    assert len(scoped) > 3000
    assert all(ev["scope"].startswith("jit(tick)/") or "/" in ev["scope"]
               for ev in scoped)
    names = {s["name"] for s in PT.pt_spans(doc)}
    assert {"step/drain", "step/admit", "step/chunks", "step/grow",
            "step/build", "step/dispatch"} <= names


@pytest.mark.parametrize("scope,name", [
    ("jit(tick)/cond/branch_1_fun/while/body/closed_call/blk/attn/gather:",
     "blk/attn"),
    ("jit(tick)/cond/branch_1_fun/while/body/closed_call/blk/attn/"
     "blk/kv_scatter/scatter:", "blk/kv_scatter"),
    ("jit(tick)/cond/branch_1_fun/while/body/closed_call/blk/attn_out/"
     "dot_general:", "blk/attn_out"),
    ("jit(step_fn)/transpose(jvp(fwd/blocks))/while/body/"
     "transpose(jvp(blk/ffn))/dot_general:", "blk/ffn"),
    ("jit(step_fn)/jvp(fwd/head)/reduce_sum:", "fwd/head"),
    ("jit(step_fn)/opt/update/mul:", "opt/update"),
    ("jit(tick)/cond/branch_1_fun/while/body/dynamic_update_slice:", ""),
    ("", ""),
])
def test_the_innermost_scope_name_of_a_path(scope, name):
    assert PT.scope_name({"scope": scope}) == name


def test_parts_and_unscoped_add_up_to_the_ticks_time(doc):
    parts = PT.tick_parts_ms(doc)
    (plane,) = tracered.device_planes(doc)
    runs = PT.program_runs(plane, "tick")
    mean_run = sum(r["dur_ns"] for r in runs) / len(runs) / 1e6
    assert parts["runs"] == pytest.approx(mean_run)
    five = sum(parts[k] for k in PT.TICK_ORDER)
    assert five + parts["in no operation"] == pytest.approx(mean_run)
    assert five == pytest.approx(mean_run, rel=0.02)
    # against a slow computation written out here: operations do not
    # overlap on this chip's line, so a part is the sum of its durations
    slow = dict.fromkeys(PT.TICK_ORDER, 0.0)
    for ev in tracered.op_events(plane):
        if any(r["start_ns"] <= ev["start_ns"] < r["start_ns"] + r["dur_ns"]
               for r in runs):
            slow[PT.tick_part(ev)] += ev["dur_ns"] / 1e6 / len(runs)
    for k in PT.TICK_ORDER:
        assert parts[k] == pytest.approx(slow[k], rel=1e-6), k
    # what the trace says of today's tick: XLA's own copies and the scan's
    # slicing and stacking of the pools, which carry no name of the
    # program, are most of it
    assert parts["unscoped"] > parts["attn"] > parts["dense"] > \
        parts["head_sample"] > parts["kv_scatter"] > 0


def _parts_ms_by_walking(doc, word, label_of, order):
    """``parts_ms`` as it stood until PR 53: an operation laid against the
    runs by ``intersection_ns``, which walks them all (and sorts them anew)
    for every operation. Kept here as the reference."""
    from collections import defaultdict
    planes = tracered.device_planes(doc)
    out = defaultdict(float)
    for p in planes:
        runs = PT.whole_runs(PT.program_runs(p, word))
        inside = tracered.merge(tracered.intervals(runs))
        by_label = defaultdict(list)
        for ev in tracered.op_events(p):
            iv = (ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
            if tracered.intersection_ns([iv], inside):
                by_label[label_of(ev)].append(iv)
        covered = []
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
    return {k: v / len(planes) for k, v in out.items()}


RECORDED = ["recorded_scoped_tick.json.gz"] + sorted(
    os.path.join("recorded_served", f)
    for f in os.listdir(os.path.join(HERE, "recorded_served"))
    if f.endswith(".json.gz"))


@pytest.mark.parametrize("piece", RECORDED)
def test_the_parts_are_what_walking_every_run_gave(piece):
    """Every recorded piece, cut by every label function there is: the
    same parts to the last digit (the pass is linear in the operations
    since PR 53; it was operations x runs)."""
    doc = PT.load_recorded(os.path.join(HERE, piece))
    cuts = [(PT.tick_part, PT.TICK_ORDER)] + [
        (tr.part, tr.ORDER) for tr in (
            loader.load_module("layer_metrics", n)
            for n in loader.load_module("layer_metrics",
                                        "_served").helpers())]
    assert len(cuts) >= 6       # the GPT tick's and five served families'
    for label_of, order in cuts:
        want = _parts_ms_by_walking(doc, "tick", label_of, order)
        assert want["n_runs"] >= 2
        assert PT.parts_ms(doc, "tick", label_of, order) == want
    # every pass is counted, by the module of its label function
    assert PT.cuts_of(doc) == [
        label_of.__module__.rsplit(".", 1)[-1] for label_of, _ in cuts]


@pytest.mark.parametrize("piece", RECORDED)
def test_a_helper_looks_for_its_mechanism_before_it_cuts(piece):
    """``names_scope`` (one look at the trace's distinct scope paths) says
    what the cut said: the helper whose mechanism's part has device time is
    the one that finds an operation under its mechanism's scope, none on
    the GPT tick; so a traced run cuts its trace for its own helper alone
    (PR 53: three and four passes, 11-14 s each on Ling's trace)."""
    doc = PT.load_recorded(os.path.join(HERE, piece))
    helpers = [loader.load_module("layer_metrics", n)
               for n in loader.load_module("layer_metrics",
                                           "_served").helpers()]
    looked = [tr.__name__.rsplit(".", 1)[-1] for tr in helpers
              if PT.names_scope(doc, tr._SCOPE, tr.MECHANISM)]
    assert PT.cuts_of(doc) == []                    # a look is no pass
    by_cut = []
    for tr in helpers:
        parts = PT.parts_ms(doc, "tick", tr.part, tr.ORDER)
        if any(parts.get(_PART_OF[m]) for m in tr.MECHANISM):
            by_cut.append(tr.__name__.rsplit(".", 1)[-1])
    cell = os.path.basename(piece)[:-len(".json.gz")]
    assert looked == by_cut == (
        [_HELPER_OF[cell]] if cell in _HELPER_OF else [])


#: a recorded cell -> the helper that reads its tick (the GPT tick: none)
_HELPER_OF = {"serve-dots3-longdoc-backlog": "_dots3_trace",
              "serve-dsv2-docqa-backlog": "_dsv2_trace",
              "serve-olmo-hybrid-gen-backlog": "_olmoh_trace",
              "serve-ling3-longgen-backlog": "_ling3_trace",
              "serve-falcon-h1-gen-backlog": "_falcon_h1_trace"}
#: a mechanism's scope -> the part its helper's cut gives it
_PART_OF = {"blk/attn/mla": "mla", "blk/attn/mla_chunk": "mla_chunk",
            "blk/attn/mla_decode": "mla_decode", "blk/gdn/step": "gdn_step",
            "blk/kda/step": "kda_step", "blk/ssd/step": "ssd_step"}


def test_a_run_cut_by_the_traces_edge_is_left_out(doc):
    """As the chip's traces begin: with the recorded tail of a tick that
    was running when the profiler started."""
    plane = doc["planes"][0]
    first = min(e["start_ns"] for ln in plane["lines"]
                for e in ln["events"])
    cut = {"name": "jit_tick(1)", "start_ns": first - 5_000_000,
           "dur_ns": 4_400_000}
    op = {"name": "%copy.116 = bf16[24,1537,16,16,128]{4,3,2,1,0} copy(...)",
          "scope": "", "start_ns": cut["start_ns"], "dur_ns": 4_000_000}
    more = {"planes": [{"name": plane["name"], "lines": [
        {"name": ln["name"], "events": ln["events"] + [
            cut if ln["name"] == "XLA Modules" else op]}
        for ln in plane["lines"]]}] + doc["planes"][1:]}
    assert len(PT.program_runs(more["planes"][0], "tick")) == 3
    assert PT.tick_parts_ms(more) == pytest.approx(PT.tick_parts_ms(doc))


def test_overlapping_operations_are_counted_once():
    ev = lambda name, scope, s, d: {"name": name, "scope": scope,  # noqa
                                    "start_ns": s, "dur_ns": d}
    doc = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [ev("jit_tick(1)", "", 0, 100)]},
        {"name": "XLA Ops", "events": [
            ev("%a = f32[1] add(...)", "jit(tick)/blk/attn/add:", 0, 60),
            ev("%b = f32[1] add(...)", "jit(tick)/blk/ffn/add:", 40, 40),
            ev("%c = f32[1] copy(...)", "", 90, 5),
            ev("%d = f32[1] add(...)", "jit(tick)/blk/qkv/add:", 200, 9)]}]}]}
    parts = PT.tick_parts_ms(doc)
    assert parts["attn"] * 1e6 == pytest.approx(60)
    assert parts["dense"] * 1e6 == pytest.approx(20)   # 40 less 20 shared
    assert parts["unscoped"] * 1e6 == pytest.approx(5)
    assert parts["in no operation"] * 1e6 == pytest.approx(15)


def test_ticks_are_laid_against_the_devices_runs(doc):
    al = PT.align_ticks(doc)
    (plane,) = tracered.device_planes(doc)
    runs = PT.program_runs(plane, "tick")
    assert al["offset"] == 0 and al["unmatched_runs"] == 0
    assert len(al["anchors"]) == 2 and set(al["anchors"]) == {0}
    assert [al["run_of"][al["first"] + i] for i in range(2)] == runs
    # each tick was dispatched before its run began, and drained after
    # its run ended: by a transfer, not by a tick
    spans = PT.pt_spans(doc)
    for tick, run in al["run_of"].items():
        (sent,) = [s for s in spans if s["name"] == "step/dispatch"
                   and s["stats"]["tick"] == tick]
        assert sent["start_ns"] < run["start_ns"]
    lags = PT.handoff_lags_ms(doc)
    assert len(lags) == 2 and all(0 < lag < 5 for lag in lags)
    # a trace that began with a tick in flight: without the first tick's
    # dispatch span the second tick is the first traced, and the second run
    first = min(s["start_ns"] for s in spans
                if s["name"] == "step/dispatch")
    late = {"planes": [p if p["name"].startswith("/device:") else {
        "name": p["name"], "lines": [{"name": ln["name"], "events": [
            e for e in ln["events"] if e["start_ns"] != first]}
            for ln in p["lines"]]} for p in doc["planes"]]}
    al = PT.align_ticks(late)
    assert al["offset"] == 1 and al["run_of"][al["first"]] == runs[1]


def test_host_time_and_idle_by_span(doc):
    spans = PT.pt_spans(doc)
    ticks = sum(s["name"] == "step/dispatch" for s in spans)
    mine = sum(s["end_ns"] - s["start_ns"] for s in spans
               if s["name"] in PT.HOST_PHASES)
    assert not [s for s in spans if s["name"] == "step/drain"
                and not s["stats"]["waited"]]
    assert PT.host_ms_per_tick(doc) == pytest.approx(mine / 1e6 / ticks)
    idle = PT.idle_by_pt_span(doc)
    win = tracered.window_of(doc)
    assert sum(idle.values()) == pytest.approx(
        (win[1] - win[0]) / 1e9 - tracered.busy_s(doc))
    assert PT.idle_outside_pct(doc) == pytest.approx(
        100 * idle.get(PT.NO_SPAN, 0.0) / sum(idle.values()))


@pytest.mark.parametrize("name", sorted(TICK_METRICS))
def test_a_tick_part_reader_on_the_recorded_piece(doc, monkeypatch, name):
    run = traced_run(doc, monkeypatch)
    value = loader.load_module("layer_metrics", name).read(run)
    assert value == pytest.approx(PT.tick_parts_ms(doc)[TICK_METRICS[name]])
    assert len(run["notes"]) == 1 and "tick parts" in run["notes"][0]


def test_the_span_readers_on_the_recorded_piece(doc, monkeypatch):
    run = traced_run(doc, monkeypatch)
    read = lambda name: loader.load_module(  # noqa: E731
        "layer_metrics", name).read(run)
    assert 0 < read("tick.handoff_lag_ms_p50") < 5
    assert read("sched.host_ms_per_tick") == PT.host_ms_per_tick(doc)
    assert read("sched.idle_outside_program_spans_pct") == \
        PT.idle_outside_pct(doc)
    assert any("anchors" in n for n in run["notes"])
    assert any(n.startswith("idle seconds by") for n in run["notes"])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_that_names_nothing_reads_as_nothing(monkeypatch, name):
    """The parent of the PR that brought the names: no scope on any
    operation, no ``pt:`` span. And an untraced run."""
    with open(os.path.join(HERE, "recorded_serve_tick.json")) as f:
        import json
        old = json.load(f)
    run = traced_run(old, monkeypatch, traced_steps=3)
    assert loader.load_module("layer_metrics", name).read(run) is None
    blind = {"ctx": types.SimpleNamespace(trace_doc=None), "facts": {}}
    assert loader.load_module("layer_metrics", name).read(blind) is None


def test_step_parts_by_kernel_name_and_scope():
    ev = lambda name, scope, s, d: {"name": name, "scope": scope,  # noqa
                                    "start_ns": s, "dur_ns": d}
    call = ' = bf16[32,2048,128]{2,1,0} custom-call(bf16[32,2048,128] %q), ' \
        'custom_call_target="tpu_custom_call"'
    blk = "jit(step_fn)/jvp(fwd/blocks)/while/body/closed_call/blk/"
    doc = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules",
         "events": [ev("jit_step_fn(7)", "", 0, 1000)]},
        {"name": "XLA Ops", "events": [
            ev("%flash_fwd.18" + call, blk + "attn/flash_fwd/pallas_call:",
               0, 100),
            ev("%flash_bwd_dkv.9" + call, "jit(step_fn)/transpose(jvp("
               "fwd/blocks))/while/body/transpose(jvp(blk/attn))/"
               "flash_bwd_dkv/pallas_call:", 100, 150),
            ev("%flash_bwd_dq.9" + call, "", 250, 50),
            ev("%fusion.1 = bf16[2] fusion(...)", blk + "ffn/dot_general:",
               300, 400),
            ev("%fusion.2 = bf16[2] fusion(...)",
               "jit(step_fn)/jvp(fwd/head)/dot_general:", 700, 80),
            ev("%fusion.3 = bf16[2] fusion(...)",
               "jit(step_fn)/jvp(fwd/stem)/gather:", 780, 20),
            ev("%fusion.4 = bf16[2] fusion(...)",
               "jit(step_fn)/opt/update/mul:", 800, 60),
            ev("%fusion.5 = bf16[2] fusion(...)", "jit(step_fn)/jvp("
               "fwd/blocks)/while/body/dynamic_update_slice:", 860, 90),
        ]}]}]}
    parts = PT.step_parts_ms(doc, 2)
    want = {"flash_fwd": 100, "flash_bwd": 200, "dense": 400, "head": 100,
            "opt": 60, "unscoped": 90, "in no operation": 50, "runs": 1000}
    assert {k: v * 2e6 for k, v in parts.items()} == pytest.approx(want)
    assert PT.kernel_ms(doc, PT.FLASH_FWD, 2) * 2e6 == pytest.approx(100)
    assert PT.kernel_ms(doc, PT.FLASH_BWD, 2) * 2e6 == pytest.approx(200)
    # the existing reader of every Mosaic call is their sum
    assert tracered.kernel_s(doc, tracered.is_mosaic_call) * 1e9 == \
        pytest.approx(300)


def test_metadata_table_is_read_from_the_wire_format(tmp_path):
    """An xplane with one device plane, one event metadata with a
    ``tf_op`` stat, written out by hand in protobuf's wire format."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def field(no, payload):
        if isinstance(payload, int):
            return varint(no << 3) + varint(payload)
        return varint(no << 3 | 2) + varint(len(payload)) + payload

    name = b"%fusion.1 = bf16[2]{0} fusion(bf16[2]{0} %p), kind=kLoop"
    stat = field(1, 9) + field(5, b"jit(tick)/blk/ffn/dot_general:")
    other = field(1, 4) + field(3, 77)
    meta = field(1, 300) + field(2, name) + field(5, other) + field(5, stat)
    plane = (field(1, 1) + field(2, b"/device:TPU:0")
             + field(4, field(1, 300) + field(2, meta))
             + field(5, field(1, 9) + field(2, field(1, 9)
                                            + field(2, b"tf_op")))
             + field(5, field(1, 4) + field(2, field(1, 4)
                                            + field(2, b"flops"))))
    host = field(1, 2) + field(2, b"/host:CPU")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, plane) + field(1, host))
    assert PT.op_scopes(str(path)) == {"/device:TPU:0": {
        name.decode(): "jit(tick)/blk/ffn/dot_general:"}}
