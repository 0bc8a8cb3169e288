"""The harness end to end: it refuses to run without a TPU, and in a
temporary copy a cell, a configuration, a traffic mix, a generator and a
per-layer metric that are added as files (and BENCHMARK.json entries) are
found and run with no edit to any file that was there."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import loader

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("configs", "traffic", "generators", "layer_metrics")


def last_json(text: str):
    lines = [ln for ln in text.strip().splitlines() if ln.strip()]
    try:
        return json.loads(lines[-1]) if lines else None
    except ValueError:
        return None


def test_run_exits_non_zero_and_prints_no_result_on_a_cpu_backend():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "train-1chip-bf16", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=loader.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert last_json(p.stdout) is None


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """perfbench/ and BENCHMARK.json copied, the toy files added beside
    the shipped ones, the toy entries appended to the lists."""
    dst = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(loader.ROOT, "perfbench"),
                    dst / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.relpath(os.path.join(d, f), dst): os.path.getmtime(
        os.path.join(d, f)) for d, _, fs in os.walk(dst) for f in fs}
    for kind in KINDS:
        for f in os.listdir(os.path.join(HERE, "toy", kind)):
            target = dst / "perfbench" / kind / f
            assert not target.exists()
            shutil.copy(os.path.join(HERE, "toy", kind, f), target)
    bench = loader.load_json(loader.root_file("BENCHMARK.json"))
    add = loader.load_json(os.path.join(HERE, "toy",
                                        "benchmark_entries.json"))
    for group in ("configs", "workloads", "per_layer"):
        bench[group] += add[group]
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] += add[group + "_workloads"].get(m["name"], [])
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    yield dst
    after = {rel: os.path.getmtime(dst / rel) for rel in before}
    assert after == before, "a shipped file was edited"


def rehearse(copy, workload, trace, devices=1, seconds="1.5"):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(copy), loader.ROOT])}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_rehearsal.py"),
         str(devices), "--workload", workload, "--seed", str(2 ** 31 + 7),
         "--seconds", seconds, "--trace", str(trace)],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = last_json(p.stdout)
    assert line is not None, p.stdout[-2000:]
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["device"]["platform"] == "cpu"     # a rehearsal, no number
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is True, p.stdout[-2000:]
    return line


@pytest.mark.parametrize("workload,trace,metrics", [
    ("toy-train-cell", 0, {"train_tokens_per_s_per_chip", "setup_s"}),
    ("toy-pp2tp2-cell", 0, {"train_tokens_per_s_per_chip", "setup_s"}),
    ("toy-train-cell", 1, {"proc.compiles_in_window", "toy.steps"}),
    ("toy-chat-cell", 0, {"itl_p95_ms", "setup_s"}),
    ("toy-chat-cell", 1, {"proc.compiles_in_window",
                          "sched.ttft_p85_ms",
                          "sched.queue_wait_p50_ms",
                          "load.generator_late_ms_max",
                          "pool.live_kv_pct.chat",
                          "sched.hold_lost_ms_in_window",
                          "sched.hold_unexplained_pct"}),
    ("toy-backlog-cell", 0, {"serve_tokens_per_s", "setup_s"}),
    ("toy-backlog-cell", 1, {"proc.compiles_in_window",
                             "served.prefill_tokens_per_tick",
                             "served.decode_rows_per_tick",
                             "served.tokens_per_s_slice_p50",
                             "pool.live_kv_pct.backlog",
                             "served.hold_lost_ms_in_window",
                             "served.hold_unexplained_pct",
                             "served.tokens_per_s_outside_holds",
                             "served.tick_ms_p50_in_window"}),
])
def test_added_files_are_found_and_the_cell_runs(copy, workload, trace,
                                                 metrics):
    """Readers that find nothing to read (no device in the trace on a CPU)
    return nothing and are left out of the line; the rest are there."""
    cells = json.loads((copy / "BENCHMARK.json").read_text())["workloads"]
    chips = next(c["chips"] for c in cells if c["name"] == workload)
    line = rehearse(copy, workload, trace, devices=chips)
    assert line["device"]["count"] == chips
    assert set(line["metrics"]) == metrics
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]
    assert line["metrics"]["proc.compiles_in_window"]["value"] == 0 \
        if trace else line["metrics"]["setup_s"]["value"] > 0
