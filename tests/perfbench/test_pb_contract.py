"""BENCHMARK.json against the rules its readers hold it to, and against the
files its names must find."""
import os
import re

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


def test_every_name_finds_its_file(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("perfbench/configs/")
        cfg = loader.load_json(loader.root_file(c["file"]))
        assert cfg["name"] == c["name"] and cfg["source"]
        assert cfg["reduced"] == c["reduced"] and "assumed" in cfg
        loader.load_module("families", cfg["family"])
        loader.load_module("checks", cfg["family"])
        loader.load_module("references", cfg["reference"])
        assert cfg["hidden_size"] == cfg["num_heads"] * cfg["head_dim"]
        assert cfg["ffn_hidden_size"] == 4 * cfg["hidden_size"]
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
