"""``benchmarks/tick_kinds.py``: a traced run's ticks by the kind the
engine's tick log gives them, cut by the benchmark's own ``parts_ms``. On a
recorded piece of a trace (three ticks of the Olmo-Hybrid cell), with the
log's rows handed in."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import tick_kinds                                               # noqa: E402
from perfbench import loader                                    # noqa: E402

PT = loader.load_module("layer_metrics", "_program_trace")
OT = loader.load_module("layer_metrics", "_olmoh_trace")


@pytest.fixture(scope="module")
def doc():
    return PT.load_recorded(os.path.join(
        ROOT, "tests", "perfbench", "recorded_served",
        "serve-olmo-hybrid-gen-backlog.json.gz"))


def test_a_ticks_kind_is_the_logs_chunk_tokens():
    rows = {"tick": np.array([-1, 7, 8, 9]),
            "chunk_tokens": np.array([0, 256, 0, 31])}
    assert tick_kinds.carried_a_chunk(rows) == {7: True, 8: False, 9: True}


def test_the_kinds_parts_add_up_to_the_benchmarks_mean_tick(doc):
    ticks = sorted(PT.align_ticks(doc)["run_of"])
    assert len(ticks) == 3
    chunked = {ticks[0]: True, ticks[1]: False, ticks[2]: False,
               ticks[2] + 1: True}          # a tick the trace never saw
    table = tick_kinds.by_kind(PT, doc, chunked, OT.part, OT.ORDER)
    with_, without = table["with a chunk"], table["without a chunk"]
    assert (with_["n"], without["n"]) == (1, 2)
    assert with_["share"] == pytest.approx(1 / 3)
    whole = PT.parts_ms(doc, "tick", OT.part, OT.ORDER)
    for part in OT.ORDER + ("in no operation",):
        both = with_["parts"][part] + 2 * without["parts"][part]
        assert both == pytest.approx(whole[part], rel=1e-9), part
    assert with_["tick_mean"] + 2 * without["tick_mean"] \
        == pytest.approx(whole["runs"], rel=1e-9)
    # the pass before the rule read a chunk in the first tick alone
    assert with_["parts"]["gdn_prep"] > 1.5 * without["parts"]["gdn_prep"]


def test_a_kind_no_traced_tick_had_is_left_out(doc):
    ticks = sorted(PT.align_ticks(doc)["run_of"])
    table = tick_kinds.by_kind(PT, doc, dict.fromkeys(ticks, False),
                               OT.part, OT.ORDER)
    assert list(table) == ["without a chunk"] and table[
        "without a chunk"]["n"] == 3
