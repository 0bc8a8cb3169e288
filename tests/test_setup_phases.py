"""Set-up on the program's own clock (ISSUE 35): ``profiler.trace.phase``
records the edges of set-up in the always-on event log whether or not
``profiler.enable()`` is on; the parameters' making adds to counters; every
dispatch site's first call is a phase that recompile.py's listener charges
the compilation's seconds to; and nothing of it happens on a tick or a
step."""
import json
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
from paddle_tpu.distributed.mesh import create_mesh
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.profiler import events, recompile, registry, trace, xla_stats
from paddle_tpu.serving import ServingConfig, ServingEngine

CFG = dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
           max_seq_len=64)
COMPILE_COUNTERS = ("compile/programs", "compile/cache_hits",
                    "compile/cache_misses", "compile/trace_s",
                    "compile/lower_s", "compile/backend_s",
                    "compile/cache_fetch_s")
DURATIONS = ("trace_s", "lower_s", "backend_s", "cache_fetch_s")


def counters(prefixes=("compile/", "setup/")) -> dict:
    return {k: v["value"] for k, v in registry().snapshot().items()
            if k.startswith(prefixes)}


def phases_since(seq: int) -> list:
    return [e.attrs for e in events.log().events(kind="phase",
                                                 since_seq=seq)]


def toy_engine(net, **kw):
    return ServingEngine(net, ServingConfig(num_slots=2, page_size=8,
                                            pages_per_slot=8, **kw))


def serve(eng, n_requests: int, new: int = 6) -> None:
    for i in range(n_requests):
        eng.submit((np.arange(12, dtype=np.int32) + i) % 128, new)
    eng.run()
    eng.reset_results()


@pytest.fixture(scope="module")
def story():
    """A ``GPT`` built eagerly and one under ``LazyGuard``, an engine on
    each run for a few ticks (the second through a page copy), a trainer
    stepped three times: all with ``profiler.enable()`` off. Then 50
    further ticks and 3 further steps."""
    if trace.is_enabled():          # a test of another file left it on
        trace.disable()
    seq0, before = events.log().next_seq, counters()
    s = {"seq0": seq0, "before": before}

    paddle.seed(0)
    eager = GPT(GPTConfig(**CFG))
    eager.eval()
    s["eager_bytes"] = sum(p._value.nbytes for p in eager.parameters())
    s["after_eager"] = counters()
    eager.bfloat16()
    with paddle.LazyGuard():
        lazy = GPT(GPTConfig(**CFG))
        lazy.eval()
        lazy.bfloat16()
    s["after_models"] = counters()

    eng = toy_engine(lazy, prefix_cache=False)   # no page copy, ever
    serve(eng, 2)
    eng2 = toy_engine(eager, prefix_cache=True)
    first = np.arange(20, dtype=np.int32)        # 2.5 pages
    second = first.copy()
    second[12:] = (second[12:] + 1) % 128        # parts inside a cached page
    for prompt in (first, second):
        eng2.submit(prompt, 3)
        eng2.run()
    eng2.reset_results()

    paddle.seed(1)
    model = GPT(GPTConfig(**CFG))
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    tr = HybridPipelineTrainer(
        model, opt, DistributedStrategy(),
        create_mesh({"dp": 1}, jax.devices()[:1]), n_micro=1,
        free_eager=True)
    toks = np.random.RandomState(0).randint(0, 128, (4, 32)).astype(np.int32)
    for _ in range(3):
        jax.block_until_ready(tr.step(toks))

    s.update(eng=eng, eng2=eng2, tr=tr, phases=phases_since(seq0),
             settled=counters())
    seq1, ticks0 = events.log().next_seq, \
        registry().counter("serving/ticks").value
    while registry().counter("serving/ticks").value - ticks0 < 50:
        serve(eng, 2, new=10)
    for _ in range(3):
        jax.block_until_ready(tr.step(toks))
    s.update(later_phases=phases_since(seq1), later=counters(),
             compiles=[e.attrs for e in events.log().events(
                 kind="compile", since_seq=seq0)])
    return s


def by_name(phases, name):
    return [p for p in phases if p["name"] == name]


@pytest.mark.parametrize("name", [
    "setup/engine", "setup/engine/decode_state", "setup/engine/pools",
    "setup/trainer", "setup/trainer/stack_blocks",
    "setup/trainer/place_others", "setup/trainer/opt_state",
    "setup/trainer/free_eager", "setup/first_call"])
def test_the_phase_is_recorded_with_profiling_off(story, name):
    assert not trace.is_enabled()
    mine = by_name(story["phases"], name)
    assert mine, sorted({p["name"] for p in story["phases"]})
    for p in mine:
        assert p["t1_ns"] >= p["t0_ns"] and p["tid"]


def test_children_name_their_parent_and_lie_inside_it(story):
    phases = {p["id"]: p for p in story["phases"]}
    assert len(phases) == len(story["phases"])          # ids are unique
    for p in phases.values():
        kind = p["name"].rsplit("/", 1)[0]
        if kind in ("setup/engine", "setup/trainer"):
            parent = phases[p["parent"]]
            assert parent["name"] == kind
            assert parent["t0_ns"] <= p["t0_ns"] <= p["t1_ns"] \
                <= parent["t1_ns"]
        else:                       # built and first called at top level
            assert p["parent"] is None, p


def test_sibling_phases_do_not_overlap(story):
    groups = {}
    for p in story["phases"]:
        groups.setdefault((p["tid"], p["parent"]), []).append(p)
    for sibs in groups.values():
        sibs.sort(key=lambda p: p["t0_ns"])
        for a, b in zip(sibs, sibs[1:]):
            assert a["t1_ns"] <= b["t0_ns"], (a, b)


def test_the_constructor_s_children_come_in_the_order_of_the_code(story):
    (tr,) = by_name(story["phases"], "setup/trainer")
    kids = sorted((p for p in story["phases"] if p["parent"] == tr["id"]),
                  key=lambda p: p["t0_ns"])
    assert [p["name"].rsplit("/", 1)[1] for p in kids] == [
        "stack_blocks", "place_others", "opt_state", "free_eager"]
    assert tr["site"] == story["tr"]._prof_site


def test_every_dispatch_site_s_first_call_carries_its_site(story):
    sites = [p["site"] for p in by_name(story["phases"],
                                        "setup/first_call")]
    eng, eng2, tr = story["eng"], story["eng2"], story["tr"]
    assert sorted(sites) == sorted([
        eng._tick_site, eng2._tick_site, eng2._copy_site, tr._prof_site])
    # a second engine in the process gets its own
    assert eng._tick_site != eng2._tick_site
    engines = {p["site"] for p in by_name(story["phases"], "setup/engine")}
    assert engines == {eng._tick_site, eng2._tick_site}


def test_the_inventory_has_each_first_call_s_compilation(story):
    for p in by_name(story["phases"], "setup/first_call"):
        rec = xla_stats.get(p["site"])
        assert rec is not None and rec.programs >= 1, p["site"]
        assert rec.cache_hit in (True, False)
        for k in DURATIONS:
            assert getattr(rec, k) >= 0.0
            assert registry().gauge(f"xla/{p['site']}/{k}").value \
                == getattr(rec, k)
        # no cache here: it was traced, lowered and compiled, inside the
        # phase
        assert rec.trace_s > 0 and rec.backend_s > 0
        took = (p["t1_ns"] - p["t0_ns"]) / 1e9
        assert rec.trace_s + rec.lower_s + rec.backend_s \
            + rec.cache_fetch_s <= took
        mine = [c for c in story["compiles"] if c["site"] == p["site"]]
        assert len(mine) == rec.programs
        assert sum(c["backend_s"] for c in mine) == \
            pytest.approx(rec.backend_s)


def test_compile_ms_is_what_the_process_paid_not_the_diagnostic(story):
    eng = story["eng"]
    paid = xla_stats.get(eng._tick_site)
    ms = (paid.backend_s + paid.cache_fetch_s) * 1e3
    assert paid.compile_ms == pytest.approx(ms) and ms > 0
    inv = eng.record_program_stats()            # compiles it once more
    assert inv[eng._tick_site]["compile_ms"] == pytest.approx(ms, abs=1e-3)
    assert inv[eng._tick_site]["flops"] is not None
    assert inv[eng._tick_site]["programs"] == paid.programs
    assert recompile.EAGER not in xla_stats.inventory()
    assert registry().gauge(
        f"xla/{eng._tick_site}/compile_ms").value == pytest.approx(ms)


def test_programs_are_hits_plus_misses_and_eager_ones_are_counted(story):
    d = {k: story["settled"].get(k, 0.0) - story["before"].get(k, 0.0)
         for k in COMPILE_COUNTERS}
    assert d["compile/programs"] == \
        d["compile/cache_hits"] + d["compile/cache_misses"]
    assert d["compile/programs"] >= 4 and d["compile/backend_s"] > 0
    eager = [c for c in story["compiles"] if c["site"] == recompile.EAGER]
    assert eager and xla_stats.get(recompile.EAGER).programs >= len(eager)


def test_no_phase_and_no_compilation_on_a_tick_or_a_step(story):
    """50 further ticks and 3 further steps: the count of ``phase`` events
    and of ``compile/programs`` does not change."""
    assert story["later_phases"] == []
    for k in COMPILE_COUNTERS[:3]:
        assert story["later"].get(k, 0.0) == story["settled"].get(k, 0.0), k


def test_the_eager_draw_and_the_cast_are_counted(story):
    grew = {k: story["after_eager"].get(k, 0.0)
            - story["before"].get(k, 0.0)
            for k in ("setup/weights_s{where=host}",
                      "setup/weights_bytes{where=host}")}
    assert grew["setup/weights_s{where=host}"] > 0
    assert grew["setup/weights_bytes{where=host}"] == story["eager_bytes"]
    assert story["after_models"]["setup/cast_s"] \
        > story["after_eager"].get("setup/cast_s", 0.0)
    # a model under LazyGuard draws nothing while it is built
    assert story["after_models"]["setup/weights_bytes{where=host}"] \
        == story["after_eager"]["setup/weights_bytes{where=host}"]


def test_a_lazy_model_is_drawn_on_the_device_inside_decode_state(story):
    label = "{where=device,phase=setup/engine/decode_state}"
    after, before = story["settled"], story["before"]
    assert after["setup/weights_s" + label] \
        > before.get("setup/weights_s" + label, 0.0)
    eng = story["eng"]
    drawn = sum(a.nbytes for a in jax.tree_util.tree_leaves(
        (eng._stacked, eng._other)))
    assert after["setup/weights_bytes" + label] \
        - before.get("setup/weights_bytes" + label, 0.0) == drawn
    # and the draw's seconds fit inside the phase that holds them
    inside = [p for p in by_name(story["phases"],
                                 "setup/engine/decode_state")]
    assert after["setup/weights_s" + label] \
        - before.get("setup/weights_s" + label, 0.0) \
        <= max(p["t1_ns"] - p["t0_ns"] for p in inside) / 1e9


def test_scope_is_still_the_shared_no_op(story):
    assert not trace.is_enabled()
    assert trace.scope("x", tick=1) is trace.scope("y") is trace._NO_SPAN
    assert trace.phase("setup/x") is not trace.phase("setup/x")


def test_a_child_left_open_goes_with_its_parent():
    seq = events.log().next_seq
    with trace.phase("setup/outer"):
        trace.phase("setup/outer/left_open").begin()
    assert trace.open_phases() == []
    with trace.phase("setup/next", site="s#1"):
        assert [p.name for p in trace.open_phases()] == ["setup/next"]
    got = phases_since(seq)
    assert [p["name"] for p in got] == ["setup/outer", "setup/next"]
    assert got[1]["parent"] is None and got[1]["site"] == "s#1"


def test_charge_setup_labels_with_the_open_phase():
    reg = registry()
    trace.charge_setup("probe", 0.25, 10, where="host")
    with trace.phase("setup/holder"):
        trace.charge_setup("probe", 0.5, 20, where="host")
    assert reg.counter("setup/probe_s{where=host}").value == 0.25
    assert reg.counter(
        "setup/probe_s{where=host,phase=setup/holder}").value == 0.5
    assert reg.counter(
        "setup/probe_bytes{where=host,phase=setup/holder}").value == 20


def test_a_phase_is_recorded_in_memory_too_while_enabled():
    seq = events.log().next_seq
    trace.enable()
    try:
        with trace.phase("setup/seen", site="x#0"):
            pass
    finally:
        summary = trace.disable()
    trace.reset_events()
    assert summary["setup/seen"]["count"] == 1
    assert [p["name"] for p in phases_since(seq)] == ["setup/seen"]


def test_a_session_sees_a_phase_as_every_scope(tmp_path):
    from test_engine_spans import pt_events

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.phase("setup/in_session", site="x#1"):
            pass
    finally:
        jax.profiler.stop_trace()
    seen = [(n, s) for n, s, _ in pt_events(str(tmp_path))
            if n == "setup/in_session"]
    assert seen and seen[0][1]["site"] == "x:1"    # "#" ends a TraceMe


def test_the_package_s_import_is_the_first_phase_of_a_process():
    """In a process of its own: the gauge ``proc/age_at_import_s`` and the
    phase ``setup/import`` around the package's own import, which starts no
    backend."""
    code = (
        "import json, time\n"
        "t0 = time.perf_counter_ns()\n"
        "import paddle_tpu\n"
        "t1 = time.perf_counter_ns()\n"
        "from jax._src import xla_bridge\n"
        "from paddle_tpu.profiler import events, registry\n"
        "print(json.dumps({'phases': [e.attrs for e in "
        "events.log().events(kind='phase')], 't0': t0, 't1': t1,"
        " 'age': registry().gauge('proc/age_at_import_s').value,"
        " 'backends': list(xla_bridge._backends)}))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, check=True).stdout
    got = json.loads(out.strip().splitlines()[-1])
    (imp,) = got["phases"]
    assert imp["name"] == "setup/import" and imp["parent"] is None
    assert got["t0"] <= imp["t0_ns"] <= imp["t1_ns"] <= got["t1"]
    # the package's own import is nearly all of the statement's time
    assert imp["t1_ns"] - imp["t0_ns"] >= 0.9 * (got["t1"] - got["t0"])
    assert 0.0 < got["age"] < 60.0
    assert got["backends"] == []
