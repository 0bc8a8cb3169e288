"""BENCHMARK.json against the rules its readers hold it to, and against the
files its names must find."""
import ast
import contextlib
import os
import re
import shutil
import subprocess
import sys

import pytest

from perfbench import loader

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return loader.load_json(loader.root_file("BENCHMARK.json"))


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(loader.root_file("BENCHMARK.json")) <= 64 * 1024
    assert bench["paths"] == ["perfbench", "tests/perfbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w
               for w in bench["command"])
    cells = len(bench["workloads"])
    assert sum(c["chips"] == 4 for c in bench["workloads"]) <= \
        max(1, cells // 4)


# --- what a count should hold: the format's own limits, no pin on today's
COUNTS = {
    "cells": (lambda b: len(b["workloads"]), lambda b: 24),
    "configurations": (lambda b: len(b["configs"]), lambda b: 24),
    "four-chip cells": (
        lambda b: sum(c["chips"] == 4 for c in b["workloads"]),
        lambda b: max(1, len(b["workloads"]) // 4)),
    "end-to-end metrics": (lambda b: len(b["end_to_end"]), lambda b: 16),
    "per-layer metrics": (lambda b: len(b["per_layer"]), lambda b: 128),
}


@pytest.mark.parametrize("what", sorted(COUNTS))
def test_a_count_stays_inside_what_the_format_allows(bench, what):
    """A later PR adds cells and entries: no test pins how many there are
    today, only what ``BENCHMARK.json`` may hold at all."""
    have, most = COUNTS[what]
    assert 1 <= have(bench) <= most(bench), what


def test_names_units_and_lines(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in bench[group]]
        assert len(seen) == len(set(seen)), f"duplicate name in {group}"
        names += seen
    for c in bench["workloads"]:
        names += [c["config"], c["traffic"]]
    for c in bench["configs"]:
        names += c["reduced"]
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for n in names:
        assert NAME.match(n), n
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for text in [c["why"] for c in bench["workloads"] + bench["configs"]] + \
            [c["source"] for c in bench["configs"]] + \
            [m["layer"] for m in bench["per_layer"]] + bench["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text, text


def test_metrics_fit_their_cells(bench):
    cells = {c["name"] for c in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert "output_tokens_per_s" not in e2e
    assert len(e2e) <= 5
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1

    def cells_of(m):
        return set(m.get("workloads", cells))

    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert cells_of(m) <= cells
        assert cells_of(m) <= cells_of(e2e[m["moves"]]), m["name"]
    for c in cells:
        assert sum(c in cells_of(m) for m in bench["end_to_end"]) >= 2
        assert any(c in cells_of(m) for m in bench["per_layer"])


# --- one entry a quantity a cell ---------------------------------------------
BENCH = loader.load_json(loader.root_file("BENCHMARK.json"))
CELLS = [c["name"] for c in BENCH["workloads"]]
ENTRIES = [m["name"] for m in BENCH["per_layer"]]


def cells_of(m: dict) -> set:
    return set(m.get("workloads", CELLS))


def read_of(name: str) -> ast.FunctionDef:
    path = os.path.join(loader.HERE, "layer_metrics", name + ".py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    return next(n for n in tree.body
                if isinstance(n, ast.FunctionDef) and n.name == "read")


def entries_called(name: str) -> set:
    """The other entries whose readers ``name``'s ``read`` loads
    (``load_module("layer_metrics", "<entry>")``); a ``_helper`` is no
    entry."""
    return {n.args[1].value for n in ast.walk(read_of(name))
            if isinstance(n, ast.Call) and len(n.args) == 2
            and all(isinstance(a, ast.Constant) for a in n.args)
            and n.args[0].value == "layer_metrics"
            and not n.args[1].value.startswith("_")}


@pytest.mark.parametrize("name", ENTRIES)
def test_no_entry_reads_for_a_cell_what_another_entry_reads_there(name):
    """A reader that only calls another entry's reader is that quantity
    again: allowed where the two entries' cells are apart (a cell that
    reports another end-to-end metric cannot join the list), never for
    one cell twice."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for other in entries_called(name):
        assert other in by_name, f"{name} reads {other}, which is no entry"
        both = cells_of(by_name[name]) & cells_of(by_name[other])
        assert not both, f"{name} and {other} both report in {sorted(both)}"


@pytest.mark.parametrize("cell", CELLS)
def test_no_two_readers_of_a_cell_are_the_same_code(cell):
    seen = {}
    for m in BENCH["per_layer"]:
        if cell not in cells_of(m):
            continue
        body = ast.dump(read_of(m["name"]))
        assert body not in seen, \
            f"{m['name']} and {seen[body]} read the same in {cell}"
        seen[body] = m["name"]


#: the cells that reported a share of the whole step's peak when the
#: per-layer list was folded (PR 48) and Ling's, whose share is the same
#: entry since PR 53; the two GPT serving cells have none yet (B4)
MFU_CELLS = ("train-1chip-bf16", "train-6.7b-pp2tp2", "train-olmoe-1chip-4k",
             "train-solar-open2-1chip", "serve-ouro-reason-steady",
             "serve-dots3-longdoc-backlog", "serve-dsv2-docqa-backlog",
             "serve-olmo-hybrid-gen-backlog", "serve-ling3-longgen-backlog")


@pytest.mark.parametrize("cell", MFU_CELLS)
def test_a_cell_keeps_its_share_of_the_whole_steps_peak(cell):
    shares = [m for m in BENCH["per_layer"]
              if "mfu" in m["name"] and cell in cells_of(m)]
    assert len(shares) == 1, [m["name"] for m in shares]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert cell in cells_of(e2e[shares[0]["moves"]])
    assert shares[0]["unit"] == "%" and shares[0]["better"] == "higher"


# The entries of the four GPT cells and the entries with no list, as PR 48
# found them: a later fold may not drift into them unnoticed (a change to
# what those cells report has them measured anew under half their bound).
# One change since, made by PR 48 itself after its check was refused (a
# benchmark PR has every cell measured anew, these too): ``ttft_p85_ms``
# left ``end_to_end`` for the per-layer ``sched.ttft_p85_ms``, so the chat
# cell's five entries that moved it move ``itl_p95_ms`` and take Ouro's cell
# into their lists, whose five copies of them (``*.loop``) went. Two more
# since, PR 53's (a benchmark PR too): ``flash.device_ms_per_step`` (the sum
# of ``flash.fwd_`` and ``flash.bwd_ms_per_step``) and
# ``pool.whole_pool_ops_ms_per_tick`` (the two scatters
# ``tick.kv_scatter_ms_per_tick`` times by scope) went, and the six entries
# of the holds (PR 51) list every serving cell, the two GPT ones among them.
T1, CHAT, LP, T67 = ("train-1chip-bf16", "serve-chat-steady",
                     "serve-longprompt-backlog", "train-6.7b-pp2tp2")
OLMOE, SOLAR, OURO = ("train-olmoe-1chip-4k", "train-solar-open2-1chip",
                      "serve-ouro-reason-steady")
PROC, SCHED, TICK, POOL, TRAIN, FLASH = (
    "process, compile cache", "serving scheduler (host)",
    "serving tick (device)", "paged attention / page pool",
    "trainer step (device)", "flash attention")
TPS, ITL, STPS = ("train_tokens_per_s_per_chip", "itl_p95_ms",
                  "serve_tokens_per_s")
ALL_TRAIN = (T1, T67, OLMOE, SOLAR)
NEWER = ("serve-dots3-longdoc-backlog", "serve-dsv2-docqa-backlog",
         "serve-olmo-hybrid-gen-backlog", "serve-ling3-longgen-backlog",
         "serve-falcon-h1-gen-backlog")
BACKLOGS = (LP,) + NEWER
#: the holds of the judged window as every backlog cell reports them (PR 51;
#: every serving cell since PR 53): the cells' own tests import this
BACKLOG_HOLDS = ("served.hold_lost_ms_in_window",
                 "served.hold_unexplained_pct",
                 "served.tokens_per_s_outside_holds",
                 "served.tick_ms_p50_in_window")
GPT_AND_LISTLESS = [
    ("proc.compiles_in_window", "count", "lower", "program_counter", PROC, "setup_s", None),
    ("sched.queue_wait_p50_ms", "ms", "lower", "program_span", SCHED, ITL, (CHAT, OURO)),
    ("load.generator_late_ms_max", "ms", "lower", "host_clock", SCHED, ITL, (CHAT, OURO)),
    ("tick.device_ms_p50.chat", "ms", "lower", "device_trace", TICK, ITL, (CHAT, OURO)),
    ("tick.device_ms_p50.backlog", "ms", "lower", "device_trace", TICK, STPS, (LP,)),
    ("pool.live_kv_pct.chat", "%", "higher", "program_counter", POOL, ITL, (CHAT, OURO)),
    ("pool.live_kv_pct.backlog", "%", "higher", "program_counter", POOL, STPS, (LP, NEWER[4])),
    ("train.mfu_pct", "%", "higher", "host_clock", TRAIN, TPS, (T1, T67)),
    ("train.peak_hbm_gb", "GB", "lower", "program_counter", TRAIN, TPS, ALL_TRAIN),
    ("train.live_hbm_gb", "GB", "lower", "program_counter", TRAIN, TPS, ALL_TRAIN),
    ("coll.exposed_ms_per_step", "ms", "lower", "device_trace", "parallel layout", TPS, (T67,)),
    ("tick.kv_scatter_ms_per_tick", "ms", "lower", "device_trace", TICK, STPS, (LP,)),
    ("tick.attn_ms_per_tick", "ms", "lower", "device_trace", TICK, STPS, (LP,)),
    ("tick.dense_ms_per_tick", "ms", "lower", "device_trace", TICK, STPS, (LP,)),
    ("tick.head_sample_ms_per_tick", "ms", "lower", "device_trace", TICK, STPS, (LP,)),
    ("tick.unscoped_ms_per_tick", "ms", "lower", "device_trace", TICK, STPS, (LP,)),
    ("tick.handoff_lag_ms_p50", "ms", "lower", "program_span", TICK, ITL, (CHAT, OURO)),
    ("sched.host_ms_per_tick", "ms", "lower", "program_span", SCHED, ITL, (CHAT, OURO)),
    ("sched.idle_outside_program_spans_pct", "%", "lower", "program_span", SCHED, ITL, (CHAT, OURO)),
    ("flash.fwd_ms_per_step", "ms", "lower", "device_trace", FLASH, TPS, ALL_TRAIN),
    ("flash.bwd_ms_per_step", "ms", "lower", "device_trace", FLASH, TPS, ALL_TRAIN),
    ("train.dense_ms_per_step", "ms", "lower", "device_trace", TRAIN, TPS, ALL_TRAIN),
    ("train.head_ms_per_step", "ms", "lower", "device_trace", TRAIN, TPS, ALL_TRAIN),
    ("train.opt_ms_per_step", "ms", "lower", "device_trace", TRAIN, TPS, ALL_TRAIN),
    ("train.unscoped_ms_per_step", "ms", "lower", "device_trace", TRAIN, TPS, ALL_TRAIN),
    ("sched.ttft_p85_ms", "ms", "lower", "host_clock", SCHED, ITL, (CHAT, OURO)),
    ("setup.before_program_s", "s", "lower", "program_counter", PROC, "setup_s", None),
    ("setup.import_s", "s", "lower", "program_span", PROC, "setup_s", None),
    ("setup.weights_s", "s", "lower", "program_counter", PROC, "setup_s", None),
    ("setup.build_s", "s", "lower", "program_span", PROC, "setup_s", None),
    ("setup.first_calls_s", "s", "lower", "program_span", PROC, "setup_s", None),
    ("setup.backend_compile_s", "s", "lower", "program_counter", PROC, "setup_s", None),
    ("setup.cache_fetch_s", "s", "lower", "program_counter", PROC, "setup_s", None),
    ("setup.programs_before_window", "count", "lower", "program_counter", PROC, "setup_s", None),
    ("setup.unaccounted_s", "s", "lower", "host_clock", PROC, "setup_s", None),
    # the long-prompt cell's three readers of facts are the newer cells' since
    # PR 56 (``sched.prefill_tokens_per_tick``, ``sched.decode_rows_per_tick``,
    # ``sched.serve_tokens_per_s_slice_p50`` until then); Ling's window is all
    # decode and lists no prefill
    ("served.prefill_tokens_per_tick", "tokens", "higher", "program_counter", SCHED, STPS, NEWER[:3] + NEWER[4:] + (LP,)),
    ("served.decode_rows_per_tick", "rows", "higher", "program_counter", SCHED, STPS, NEWER + (LP,)),
    ("served.tokens_per_s_slice_p50", "tokens/s", "higher", "host_clock", SCHED, STPS, NEWER + (LP,)),
    ("served.hold_lost_ms_in_window", "ms", "lower", "program_counter", SCHED, STPS, BACKLOGS),
    ("sched.hold_lost_ms_in_window", "ms", "lower", "program_counter", SCHED, ITL, (CHAT, OURO)),
    ("served.hold_unexplained_pct", "%", "lower", "program_counter", SCHED, STPS, BACKLOGS),
    ("sched.hold_unexplained_pct", "%", "lower", "program_counter", SCHED, ITL, (CHAT, OURO)),
    ("served.tokens_per_s_outside_holds", "tokens/s", "higher", "program_counter", SCHED, STPS, BACKLOGS),
    ("served.tick_ms_p50_in_window", "ms", "lower", "program_counter", TICK, STPS, BACKLOGS),
]


def test_the_gpt_cells_entries_and_the_listless_ones_are_as_they_were(bench):
    gpt = {T1, CHAT, LP, T67}
    have = [(m["name"], m["unit"], m["better"], m["source"], m["layer"],
             m["moves"], tuple(m["workloads"]) if "workloads" in m else None)
            for m in bench["per_layer"]
            if "workloads" not in m or gpt & set(m["workloads"])]
    assert have == GPT_AND_LISTLESS
    for m in bench["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"} | ({"workloads"} & set(m))


def config_file_is_sound(entry: dict, cfg: dict) -> None:
    """What every configuration file is held to: it is the entry's, it
    says what was cut and assumed, its family, check and reference are
    found, and its sizes hang together **as its family says they must**
    (``check_widths`` raises with the key's name). No width identity
    lives here: which widths multiply out to which is the architecture's
    business (the tests at the end of this file)."""
    assert cfg["name"] == entry["name"] and cfg["source"]
    assert cfg["reduced"] == entry["reduced"] and "assumed" in cfg
    family = loader.load_module("families", cfg["family"])
    loader.load_module("checks", cfg["family"])
    loader.load_module("references", cfg["reference"])
    assert callable(getattr(family, "check_widths", None)), \
        f"families/{cfg['family']}.py exports no check_widths(config)"
    assert callable(family.run)
    family.check_widths(cfg)


def test_every_name_finds_its_file(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("perfbench/configs/")
        config_file_is_sound(c, loader.load_json(loader.root_file(c["file"])))
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        traffic = loader.load_data("traffic", w["traffic"])
        loader.load_module("generators", traffic["generator"])
        cell = loader.load_cell(w["name"])
        assert cell["cell"] == w and cell["per_layer"]
    for m in bench["per_layer"]:
        assert callable(loader.load_module("layer_metrics", m["name"]).read)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_configurations_set_sizes_and_leave_policy_alone(bench):
    """No policy knob of the program appears in a configuration file."""
    policy = {"prefill_chunk", "prefill_chunks_per_tick", "scheduler",
              "attention_kernel", "attention_impl", "max_inflight", "spec",
              "num_pages", "remat_policy", "v_virtual", "unroll_layers",
              "update_scan", "offload_optimizer", "offload_params"}
    for c in bench["configs"]:
        cfg = loader.load_json(loader.root_file(c["file"]))
        knobs = set(cfg.get("engine", {})) | set(cfg.get("trainer", {}))
        assert not knobs & policy, knobs & policy
    six = loader.load_json(loader.root_file(
        "perfbench/configs/gpt3-6.7b-train.json"))
    assert six["published"]["num_layers"] == 32 and six["num_layers"] % 4 == 0
    assert six["trainer"]["mesh"] == {"pp": 2, "tp": 2}


def test_files_under_paths_have_plain_names(bench):
    ok = re.compile(r"[A-Za-z0-9_.\-/]+\Z")
    for top in bench["paths"]:
        for d, dirs, files in os.walk(loader.root_file(top)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), loader.ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel


def test_a_name_with_no_file_is_an_error_that_names_the_directory():
    with pytest.raises(loader.NotFound, match=r"layer_metrics"):
        loader.load_module("layer_metrics", "no.such.metric")
    with pytest.raises(loader.NotFound, match=r"traffic"):
        loader.load_data("traffic", "no-such-mix")
    with pytest.raises(loader.NotFound, match=r"BENCHMARK.json"):
        loader.load_cell("no-such-cell")


# --- what a family is: its model and what its sizes must satisfy; the
# --- loops, the window and the reductions are the harness's, once

HERE = os.path.dirname(os.path.abspath(__file__))
#: the three the benchmark shipped with when the door was opened: what is
#: said of *them* is said by name, so that a fourth changes nothing here
SHIPPED = ("gpt_serve", "gpt_train", "olmoe_train")
#: whatever is there now: only what every family must hold is asked of it
FAMILIES = sorted(f[:-3] for f in os.listdir(os.path.join(
    loader.HERE, "families")) if f.endswith(".py"))
GPT_KEYS = ("hidden_size", "num_heads", "head_dim", "ffn_hidden_size")
OLMOE_KEYS = ("hidden_size", "num_attention_heads", "head_dim",
              "num_key_value_heads", "num_experts_per_tok",
              "intermediate_size", "ffn_hidden_size", "num_heads")
WIDTH_KEYS = [(name, key) for name, keys in (
    ("gpt3-1.3b-train", GPT_KEYS), ("gpt3-1.3b-serve", GPT_KEYS),
    ("gpt3-6.7b-train", GPT_KEYS), ("olmoe-1b-7b-train", OLMOE_KEYS))
    for key in keys]


def toy_gqa():
    return loader.load_json(os.path.join(HERE, "toy_gqa", "configs",
                                         "toy-gqa.json"))


def copy_with_toy_gqa(dst: str) -> None:
    """``perfbench/`` copied to ``dst`` with the toy family's files added
    beside the shipped ones: a family arrives as files, with no edit to
    one that is there."""
    shutil.copytree(loader.HERE, dst,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("families", "checks"):
        shutil.copy(os.path.join(HERE, "toy_gqa", kind, "toy_gqa.py"),
                    os.path.join(dst, kind, "toy_gqa.py"))


@contextlib.contextmanager
def loader_at_a_copy_with_toy_gqa(tmp_path):
    """The loader pointed at such a copy. What it loads from there is
    forgotten on the way out: ``load_module`` keeps what it loaded in
    ``sys.modules``, and a later test must not be handed a module of a
    copy that is gone."""
    dst, real = str(tmp_path / "perfbench"), loader.HERE
    copy_with_toy_gqa(dst)
    loader.HERE = dst
    try:
        yield dst
    finally:
        loader.HERE = real
        for name in loaded_from(dst):
            del sys.modules[name]


def loaded_from(directory: str) -> list:
    return [name for name, mod in list(sys.modules.items())
            if name.startswith("perfbench.")
            and (getattr(mod, "__file__", None) or "").startswith(directory)]


@pytest.fixture
def with_toy_gqa(tmp_path):
    with loader_at_a_copy_with_toy_gqa(tmp_path) as dst:
        yield dst


def test_what_a_copy_loaded_is_forgotten_with_it(tmp_path):
    with loader_at_a_copy_with_toy_gqa(tmp_path) as dst:
        family = loader.load_module("families", "toy_gqa")
        loader.load_module("checks", "toy_gqa")
        assert family.__file__.startswith(dst) and loaded_from(dst)
    assert not loaded_from(dst) and loader.HERE != dst


def test_widths_that_are_not_gpts_pass_given_a_family_that_takes_them(
        with_toy_gqa):
    """Query heads x head size = 2 x hidden, fewer key/value heads than
    query heads, a gated FFN that is not 4 x hidden, a few of several
    experts a token: Solar-Open2's published 4,096 / 64 x 128 / 8 /
    8 of 320 x 1,280 are of this shape."""
    cfg = toy_gqa()
    assert cfg["num_attention_heads"] * cfg["head_dim"] == \
        2 * cfg["hidden_size"]
    assert cfg["num_key_value_heads"] < cfg["num_attention_heads"]
    assert cfg["intermediate_size"] != 4 * cfg["hidden_size"]
    config_file_is_sound({"name": "toy-gqa", "reduced": []}, cfg)
    for key, value in (("num_key_value_heads", 3),
                       ("num_experts_per_tok", 9)):
        with pytest.raises(ValueError, match=key):
            config_file_is_sound({"name": "toy-gqa", "reduced": []},
                                 {**cfg, key: value})


@pytest.mark.parametrize("family", SHIPPED)
def test_the_shipped_families_refuse_those_widths(family):
    """A family's identities are its own architecture's: each of the
    three refuses the toy's file, by a key's name or for the lack of a
    key it reads. Said of those three by name: a fourth family may well
    take the toy."""
    check = loader.load_module("families", family).check_widths
    with pytest.raises((ValueError, KeyError),
                       match="hidden_size|num_heads|ffn_hidden_size"):
        check(toy_gqa())


@pytest.mark.parametrize("name,key", WIDTH_KEYS)
def test_a_changed_width_is_refused_with_its_key_in_the_message(
        bench, name, key):
    """Nothing was loosened for a file that is in the benchmark: one width
    changed, whichever, and its family refuses the file."""
    entry = next(c for c in bench["configs"] if c["name"] == name)
    cfg = loader.load_json(loader.root_file(entry["file"]))
    config_file_is_sound(entry, cfg)
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        config_file_is_sound(entry, {**cfg, key: cfg[key] * 2})


def family_is_only_a_model(path: str) -> None:
    """What every file under ``families/`` is held to, a later PR's too:
    no loop over ticks or steps, no window, no profiler, no clock, and
    nothing read of the program whose name begins with ``_``."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    assert not [n.lineno for n in ast.walk(tree)
                if isinstance(n, ast.While)], "a loop over ticks or steps"
    attrs = {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    assert not attrs & {"open_window", "start_trace", "stop_trace",
                        "read_trace", "perf_counter"}
    private = {a for a in attrs
               if a.startswith("_") and not a.startswith("__")}
    assert not private, f"reads insides of the program: {sorted(private)}"


@pytest.mark.parametrize("family", FAMILIES)
def test_a_family_holds_no_loop_opens_no_window_and_reads_no_insides(family):
    family_is_only_a_model(os.path.join(loader.HERE, "families",
                                        family + ".py"))
    mod = loader.load_module("families", family)
    assert callable(mod.check_widths) and callable(mod.run)


def test_the_windows_edges_are_defined_in_the_two_loops_alone():
    opens = []
    for d, dirs, files in os.walk(loader.HERE):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(d, f), encoding="utf-8") as src:
                on_ctx = {n.attr for n in ast.walk(ast.parse(src.read()))
                          if isinstance(n, ast.Attribute)
                          and isinstance(n.value, ast.Name)
                          and n.value.id == "ctx"}
            if on_ctx & {"open_window", "start_trace"}:
                opens.append(os.path.relpath(os.path.join(d, f),
                                             loader.HERE))
    assert sorted(opens) == ["serve_loop.py", "train_loop.py"]


def test_a_fourth_family_dropped_beside_the_three_leaves_this_file_green(
        tmp_path):
    """The next ``model_config`` PR adds files and edits none: the whole
    of this file run in a copy of the benchmark that holds the toy family
    beside the shipped ones: the cases here and the toy's own under
    ``test_a_family_holds_no_loop...``, all passing."""
    copy_with_toy_gqa(str(tmp_path / "perfbench"))
    shutil.copy(loader.root_file("BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "tests" / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/perfbench/" +
         os.path.basename(__file__), "-v", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-k", "not dropped_beside"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stdout[-4000:] + done.stderr[-2000:]
    assert re.search(r"reads_no_insides\[toy_gqa\] PASSED", done.stdout)
    assert " failed" not in done.stdout.splitlines()[-1]
