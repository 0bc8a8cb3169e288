"""The nine ``setup.*`` readers on a hand-written record: the program's
event log and registry filled by hand, a context that opened its window
after them. The rows are disjoint (a second to the innermost), a phase that
did not occur reads 0.0, and the rows with the traffic's ``warm_in_s`` and
``setup.unaccounted_s`` make ``setup_s``."""
import time
import types

import pytest

from paddle_tpu.profiler import events, registry
from perfbench import loader

ROWS = ("setup.before_program_s", "setup.import_s", "setup.weights_s",
        "setup.build_s", "setup.first_calls_s")
ALL = ROWS + ("setup.backend_compile_s", "setup.cache_fetch_s",
              "setup.programs_before_window", "setup.unaccounted_s")


def read(name, run):
    return loader.load_module("layer_metrics", name).read(run)


class Record:
    """The program's record, written by hand: phases in seconds since a
    zero a minute ago, counters by name."""

    def __init__(self):
        self.log, self.reg = events.log(), registry()
        self.log.clear()
        self.reg.reset()
        self.zero_ns = time.perf_counter_ns() - 60 * 10 ** 9
        self.ids = iter(range(1000))

    def phase(self, name, t0, t1, parent=None, **ids):
        pid = next(self.ids)
        self.log.emit("phase", name=name, id=pid, parent=parent, tid=1,
                      t0_ns=self.zero_ns + int(t0 * 1e9),
                      t1_ns=self.zero_ns + int(t1 * 1e9), **ids)
        return pid

    def compile(self, site, backend_s=0.0, cache_fetch_s=0.0):
        self.log.emit("compile", site=site, trace_s=0.5, lower_s=0.25,
                      backend_s=backend_s, cache_fetch_s=cache_fetch_s,
                      cache_hit=cache_fetch_s > 0)

    def run(self, setup_s, warm_in_s=None, platform="tpu"):
        traffic = {} if warm_in_s is None else {"warm_in_s": warm_in_s}
        ctx = types.SimpleNamespace(
            t_open=time.perf_counter(), setup_s=setup_s, traffic=traffic,
            devices=[types.SimpleNamespace(platform=platform)])
        return {"ctx": ctx, "facts": {}}


@pytest.fixture
def rec():
    r = Record()
    yield r
    r.log.clear()
    r.reg.reset()


def identity(run, warm_in_s=0.0):
    return sum(read(n, run) for n in ROWS) + warm_in_s \
        + read("setup.unaccounted_s", run)


def test_a_trainer_built_from_an_eager_model(rec):
    rec.reg.gauge("proc/age_at_import_s").set(9.0)
    rec.phase("setup/import", 0.0, 2.0)
    rec.reg.counter("setup/weights_s{where=host}").add(11.0)     # 2 .. 13
    rec.reg.counter("setup/weights_bytes{where=host}").add(5e9)
    tr = rec.phase("setup/trainer", 13.0, 18.0, site="hybrid.step#0")
    rec.phase("setup/trainer/stack_blocks", 13.5, 15.5, tr)
    rec.phase("setup/trainer/opt_state", 15.5, 17.0, tr)
    for _ in range(300):
        rec.compile("eager", backend_s=0.01)
    rec.phase("setup/first_call", 18.5, 24.5, site="hybrid.step#0")
    rec.compile("hybrid.step#0", cache_fetch_s=3.0)
    run = rec.run(setup_s=9.0 + 24.5 + 4.0)      # two warm steps after it
    rec.compile("hybrid.step#0", backend_s=100.0)    # after the window opened
    rec.phase("setup/first_call", 70.0, 71.0, site="late#0")
    got = {n: read(n, run) for n in ALL}
    assert got == pytest.approx({
        "setup.before_program_s": 9.0, "setup.import_s": 2.0,
        "setup.weights_s": 11.0, "setup.build_s": 5.0,
        "setup.first_calls_s": 6.0, "setup.backend_compile_s": 3.0,
        "setup.cache_fetch_s": 3.0, "setup.programs_before_window": 301.0,
        "setup.unaccounted_s": 4.5})
    assert identity(run) == pytest.approx(run["ctx"].setup_s)


def test_an_engine_that_draws_and_first_calls_inside_its_constructor(rec):
    """``LazyGuard``: the weights are drawn inside
    ``setup/engine/decode_state``; and a first call nested in
    ``setup/engine``: neither is counted twice."""
    rec.reg.gauge("proc/age_at_import_s").set(8.0)
    rec.phase("setup/import", 0.0, 1.5)
    rec.reg.counter("setup/cast_s").add(0.5)
    eng = rec.phase("setup/engine", 2.0, 12.0, site="serving.tick#0")
    rec.phase("setup/engine/decode_state", 2.0, 7.0, eng)
    rec.reg.counter("setup/weights_s{where=device,"
                    "phase=setup/engine/decode_state}").add(4.0)
    rec.phase("setup/engine/pools", 7.0, 8.0, eng)
    rec.phase("setup/first_call", 8.5, 11.5, eng, site="fold_key#0")
    rec.phase("setup/first_call", 12.5, 20.5, site="serving.tick#0")
    run = rec.run(setup_s=8.0 + 20.5 + 1.0 + 20.0, warm_in_s=20.0)
    assert read("setup.weights_s", run) == pytest.approx(4.5)
    # 10 s of constructor, less 3 s of first call, less 4 s of weights
    assert read("setup.build_s", run) == pytest.approx(3.0)
    assert read("setup.first_calls_s", run) == pytest.approx(11.0)
    assert read("setup.unaccounted_s", run) == pytest.approx(1.5)
    assert identity(run, 20.0) == pytest.approx(run["ctx"].setup_s)


def test_a_phase_that_did_not_occur_reads_zero_not_null(rec):
    rec.reg.gauge("proc/age_at_import_s").set(7.0)
    run = rec.run(setup_s=10.0)
    got = {n: read(n, run) for n in ALL}
    assert got == {**{n: 0.0 for n in ALL}, "setup.before_program_s": 7.0,
                   "setup.unaccounted_s": 3.0}
    assert all(isinstance(v, float) for v in got.values())


@pytest.mark.parametrize("name", ALL)
def test_a_program_without_the_record_reads_nothing(rec, name):
    """The parent of the PR that brought the record: no gauge, no phase."""
    assert read(name, rec.run(setup_s=40.0)) is None


@pytest.mark.parametrize("name", ALL)
def test_a_rehearsal_off_the_chip_reads_nothing(rec, name):
    rec.reg.gauge("proc/age_at_import_s").set(7.0)
    rec.phase("setup/import", 0.0, 2.0)
    assert read(name, rec.run(setup_s=40.0, platform="cpu")) is None


def test_every_reader_is_an_entry_of_the_layer_and_nothing_else_is():
    bench = loader.load_json(loader.root_file("BENCHMARK.json"))
    mine = [m for m in bench["per_layer"]
            if m["layer"] == "process, compile cache"]
    assert [m["name"] for m in mine] == ["proc.compiles_in_window", *ALL]
    for m in mine:
        assert m["moves"] == "setup_s" and "workloads" not in m
        assert m["unit"] == ("count" if m["name"] in (
            "proc.compiles_in_window", "setup.programs_before_window")
            else "s")
