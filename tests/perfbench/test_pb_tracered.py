"""The reductions from trace to numbers, on a recorded trace.

``recorded_serve_tick.json`` holds 70 ms of the 1.3B serving tick as the
TPU v5e's profiler wrote it (PR 23, backlog cell): every operation of 0.1
ms or more, the tick's program runs, one in twelve of the short operations,
and the benchmark's own host spans; names cut to 200 characters. Each
reduction is checked against a slow computation written out here.
"""
import json
import os
import random
import types

import pytest

from perfbench import loader, tracered

POOL = (24, 1537, 16, 16, 128)
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(HERE, "recorded_serve_tick.json")) as f:
        return json.load(f)


def ops_of(doc):
    (plane,) = tracered.device_planes(doc)
    return tracered.op_events(plane)


def covered(ivs, lo, hi):
    """Nanoseconds of [lo, hi) inside any interval, by sweeping edges."""
    edges = sorted({lo, hi} | {x for iv in ivs for x in iv if lo < x < hi})
    return sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for s, e in ivs))


def test_the_recorded_trace_is_what_it_says(doc):
    assert [p["name"] for p in doc["planes"]] == ["/device:TPU:0",
                                                 "/host:CPU"]
    (plane,) = tracered.device_planes(doc)
    lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
    assert all(e["name"].startswith("jit_tick(")
               for e in lines["XLA Modules"])
    raw = lines["XLA Ops"]
    containers = [e for e in raw if tracered.opcode(e) in
                  ("while", "conditional")]
    assert containers and len(ops_of(doc)) == len(raw) - len(containers)


def test_names_shapes_and_opcodes_come_from_the_hlo_text(doc):
    by_name = {tracered.short_name(e): e for e in ops_of(doc)}
    ev = by_name["copy.117"]
    assert tracered.opcode(ev) == "copy"
    assert tracered.result_shape(ev) == ("bf16", POOL)
    assert tracered.op_label(ev) == "copy.117_bf16_24_1537_16_16_128_"
    fused = by_name["bitcast_dynamic-update-slice_fusion.9"]
    assert tracered.opcode(fused) == "fusion"
    assert tracered.result_shape(fused) == ("bf16", POOL)
    assert tracered.result_shape({"name": "jit_tick(1)"}) is None


def test_busy_is_the_union_of_operations_not_their_sum(doc):
    ops = ops_of(doc)
    ivs = tracered.intervals(ops)
    lo, hi = tracered.window_of(doc)
    assert (lo, hi) == (min(s for s, _ in ivs), max(e for _, e in ivs))
    assert tracered.busy_s(doc) * 1e9 == pytest.approx(covered(ivs, lo, hi))
    assert tracered.busy_s(doc) <= (hi - lo) / 1e9
    # a while loop spans its body's operations: counted, it would double
    (plane,) = tracered.device_planes(doc)
    everything = [e for ln in plane["lines"] if ln["name"] == "XLA Ops"
                  for e in ln["events"]]
    assert sum(e["dur_ns"] for e in everything) > hi - lo


def test_idle_gaps_go_to_the_span_that_was_open(doc):
    gaps = tracered.idle_gaps_by_span(doc)
    lo, hi = tracered.window_of(doc)
    idle = (hi - lo) / 1e9 - tracered.busy_s(doc)
    assert sum(gaps.values()) == pytest.approx(idle)
    assert set(gaps) <= {"step", "submit", "drain", "unattributed"}
    spans = tracered.host_spans(doc)
    assert {name for name, _, _ in spans} == {"step", "submit"}
    # by hand: each gap to the latest-opened span that holds its start
    busy = tracered.merge(tracered.intervals(ops_of(doc)))
    want = {}
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        holds = [s for s in spans if s[1] <= gap_start < s[2]]
        name = holds[-1][0] if holds else "unattributed"
        want[name] = want.get(name, 0.0) + (gap_end - gap_start) / 1e9
    assert gaps == pytest.approx(want)
    assert gaps["step"] > 10 * gaps.get("unattributed", 0.0)


def test_the_operations_that_took_most_time_are_named_with_their_shape(doc):
    """The recorded tick is PR 23's, whose longest operations were copies of
    a whole page pool: ``top_ops`` names an operation with its result's
    shape, which is how such a temporary shows in a run's ``breakdown``
    (the entry that summed them, ``pool.whole_pool_ops_ms_per_tick``, went
    at PR 53 with ``tracered.whole_pool_ops_s``)."""
    ops = ops_of(doc)
    whole = [e for e in ops if " = bf16[24,1537,16,16,128]{" in e["name"]]
    assert {tracered.opcode(e) for e in whole} >= {"copy", "fusion"}
    assert all(tracered.result_shape(e) == ("bf16", POOL) for e in whole)
    top = tracered.top_ops(doc, 3)
    assert top[0][0] == "copy.117_bf16_24_1537_16_16_128_"
    assert top[0][1] >= top[1][1] >= top[2][1] > 0


def test_exposed_collective_time_is_what_no_compute_covers(doc):
    """The one-chip trace has no collective; two are laid over it with
    names copied from the four-chip trainer's trace (PR 23): one inside a busy
    stretch, one reaching into the longest idle gap."""
    assert tracered.exposed_collective_s(doc) == 0.0
    busy = tracered.merge(tracered.intervals(ops_of(doc)))
    gap_start, gap_end = max(
        ((a[1], b[0]) for a, b in zip(busy, busy[1:])),
        key=lambda g: g[1] - g[0])
    gap = gap_end - gap_start
    assert gap > 1000
    long_start = max(busy, key=lambda iv: iv[1] - iv[0])[0]
    hidden = {"name": "%all-reduce.13 = bf16[24,2,2048,4096]{3,2,1,0:T(8,128)"
              "(2,1)} all-reduce(bf16[24,2,2048,4096]{3,2,1,0:T(8,128)(2,1)} "
              "%bitcast.62), channel_id=21, replica_groups=[2,2]<=[4], "
              "use_global_device_ids=true, to_apply=%add.7.clone",
              "start_ns": long_start + 10, "dur_ns": 50}
    part = {"name": "%collective-permute-done = bf16[2,2048,4096]{2,1,0:T(8,"
            "128)(2,1)S(1)} collective-permute-done((bf16[2,2048,4096]{2,1,0"
            ":T(8,128)(2,1)S(1)}, u32[]{:S(2)}) %collective-permute-start)",
            "start_ns": gap_start - 500, "dur_ns": 500 + gap // 2}
    fusion_of_one = {"name": "%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} "
                     "%all-reduce.13), kind=kLoop",
                     "start_ns": long_start + 20, "dur_ns": 5}
    assert tracered.collective_kind(hidden) == "all-reduce"
    assert tracered.collective_kind(part) == "collective-permute"
    assert tracered.collective_kind(fusion_of_one) is None
    plane = doc["planes"][0]
    more = {"planes": [{"name": plane["name"], "lines": plane["lines"] + [
        {"name": "XLA Ops", "events": [hidden, part, fusion_of_one]}]}]}
    assert tracered.exposed_collective_s(more) * 1e9 == \
        pytest.approx(gap // 2)


def test_layer_metric_readers_on_the_recorded_tick(doc):
    run = {"ctx": types.SimpleNamespace(trace_doc=doc), "facts": {}}
    tick = loader.load_module("layer_metrics", "tick.device_ms_p50.backlog")
    assert tick.read(run) == pytest.approx(57.463127)   # of 7.3, 57.46, 57.46
    # nothing to read: the reader returns nothing, the line leaves it out
    blind = {"ctx": types.SimpleNamespace(trace_doc=None), "facts": {}}
    for name in ("tick.device_ms_p50.chat", "flash.fwd_ms_per_step",
                 "flash.bwd_ms_per_step", "tick.kv_scatter_ms_per_tick",
                 "coll.exposed_ms_per_step", "sched.queue_wait_p50_ms",
                 "served.decode_rows_per_tick", "train.mfu_pct"):
        assert loader.load_module("layer_metrics", name).read(blind) is None


def test_an_operation_is_laid_against_the_runs_by_bisection():
    """``overlaps`` answers what ``intersection_ns([iv], merged) > 0``
    answered by walking every run (minutes over a traced stretch of some
    hundreds of ticks, PR 53): the same, seeded, touching ends and empty
    operations among them."""
    rng = random.Random(53)
    for _ in range(500):
        merged = tracered.merge([
            (a, a + rng.randint(1, 8))
            for a in (rng.randint(0, 60) for _ in range(rng.randint(0, 6)))])
        starts = [s for s, _ in merged]
        for _ in range(40):
            lo = rng.randint(-2, 70)
            iv = (lo, lo + rng.randint(0, 12))
            assert tracered.overlaps(iv, merged, starts) == (
                tracered.intersection_ns([iv], merged) > 0), (iv, merged)
    merged = [(10, 20), (30, 40)]
    for iv, said in (((20, 30), False), ((19, 20), True), ((20, 31), True),
                     ((5, 10), False), ((15, 15), False), ((0, 50), True),
                     ((40, 45), False)):
        assert tracered.overlaps(iv, merged, [10, 30]) is said, iv


def test_interval_arithmetic():
    assert tracered.merge([(5, 7), (1, 3), (2, 4), (7, 9)]) == \
        [(1, 4), (5, 9)]
    assert tracered.union_ns([(1, 3), (2, 4), (10, 11)]) == 4
    assert tracered.intersection_ns([(0, 10)], [(2, 4), (8, 12)]) == 4
    assert tracered.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tracered.is_mosaic_call({
        "name": '%custom-call.4 = bf16[2,2048,16,128]{3,2,1,0} custom-call('
        'bf16[2,2048,16,128] %q), custom_call_target="tpu_custom_call"'})
    assert not tracered.is_mosaic_call({
        "name": '%custom-call.30 = bf16[6,24]{1,0} custom-call(), '
        'custom_call_target="AllocateBuffer"'})
    empty = {"planes": []}
    assert tracered.window_of(empty) is None and tracered.busy_s(empty) == 0
    assert tracered.idle_gaps_by_span(empty) == {}
