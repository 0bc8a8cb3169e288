"""The looped model's cell: the configuration file against the catalog's
widths and its family's identities, a CPU rehearsal of the family, its check
and its readers on a toy configuration in a temporary copy (as
``test_pb_olmoe.py`` does), the check's controls through ``check()`` itself,
``yardstick_loop``'s counts and the ``loop.*`` readers on a synthetic
trace."""
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import loader, yardstick, yardstick_loop

from test_pb_contract import config_file_is_sound

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_ouro")
CELL = "serve-ouro-reason-steady"


def real_config():
    return loader.load_json(loader.root_file(
        "perfbench/configs/ouro-2.6b-serve.json"))


def toy_config():
    return loader.load_json(os.path.join(TOY, "configs", "toy-ouro.json"))


@pytest.fixture(scope="module")
def bench():
    return loader.load_json(loader.root_file("BENCHMARK.json"))


def test_the_configuration_is_the_catalogs_row_uncut(bench):
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b-serve")
    cfg = real_config()
    config_file_is_sound(entry, cfg)
    assert entry["reduced"] == cfg["reduced"] == []
    want = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 5632, "max_position_embeddings": 65536,
            "max_window_layers": 48, "num_attention_heads": 16,
            "num_hidden_layers": 48, "num_key_value_heads": 16,
            "rms_norm_eps": 1e-06, "rope_scaling": None,
            "rope_theta": 1000000, "sliding_window": None,
            "tie_word_embeddings": False, "total_ut_steps": 4,
            "early_exit_threshold": 1, "use_sliding_window": False,
            "vocab_size": 49152, "model_type": "ouro"}
    assert {k: cfg[k] for k in want} == want
    assert cfg["layer_types"] == ["full_attention"] * 48
    e = cfg["engine"]
    assert e["pages_per_slot"] * e["page_size"] == 512
    assert e["num_slots"] in (8, 9, 10)
    for key in ("bias", "sandwich_norm", "final_norm", "exit_gate",
                "cache_index", "initializer_range"):
        assert key in cfg["assumed"], key
    # the pools' bytes, as ISSUE 33 reckons them: 8,192 B a token a layer
    pages = e["num_slots"] * e["pages_per_slot"] + 1
    token = cfg["total_ut_steps"] * cfg["num_hidden_layers"] * 2 \
        * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    assert token == 1_572_864
    assert round(pages * e["page_size"] * token / 1e9, 2) == \
        {10: 8.08, 9: 7.27, 8: 6.47}[e["num_slots"]]


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 4096), ("head_dim", 64), ("num_attention_heads", 32),
    ("num_key_value_heads", 4), ("total_ut_steps", 0),
    ("layer_types", ["full_attention"] * 47), ("num_hidden_layers", 24)])
def test_a_changed_width_is_refused_by_its_key(bench, key, value):
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b-serve")
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        config_file_is_sound(entry, {**real_config(), key: value})


def test_the_family_builds_the_looped_model_from_the_files_sizes():
    from paddle_tpu.models import GPTConfig

    fam = loader.load_module("families", "ouro_serve")
    cfg = fam.model_config(real_config())
    assert cfg == GPTConfig.ouro_2_6b()
    assert cfg.num_params() == 2_667_974_657
    # the loop is stated once: the program's loop_steps is total_ut_steps
    assert fam.model_config(
        {**real_config(), "total_ut_steps": 2}).loop_steps == 2
    with pytest.raises(ValueError, match="full attention"):
        fam.model_config({**real_config(), "sliding_window": 4096})
    assert fam.limits(real_config()) == {
        "vocab_size": 49152, "num_slots": 10, "capacity": 512}


def test_the_traffic_fits_a_slot_and_offers_enough_requests():
    gen = loader.load_module("generators", "open_loop_poisson")
    fam = loader.load_module("families", "ouro_serve")
    params = loader.load_data("traffic", "reason-steady")
    plan = gen.generate(params, 2 ** 31 + 5, 45.0, fam.limits(real_config()))
    sizes = [(len(r["prompt"]), r["max_new"]) for r in plan["requests"]]
    assert max(p + n for p, n in sizes) <= 496
    assert min(p for p, _ in sizes) >= 16 and max(p for p, _ in sizes) <= 128
    assert min(n for _, n in sizes) >= 48 and max(n for _, n in sizes) <= 368
    due = [r for r in plan["requests"] if 20.0 <= r["due_s"] < 65.0]
    assert len(due) >= 25      # 0.56 requests/s x 45 s
    assert max(int(r["prompt"].max()) for r in plan["requests"]) > 40000


def test_the_benchmarks_reference_is_the_programs_copy():
    """Two files, one text but for the sentence that says which is the
    copy: the benchmark imports none of the program's arithmetic."""
    def body(path):
        with open(loader.root_file(path), encoding="utf-8") as f:
            text = f.read()
        return text[text.index("With ``N(.)``"):]

    mine = body("perfbench/references/ouro.py")
    assert mine == body("paddle_tpu/models/ouro_reference.py")
    assert "import paddle_tpu" not in mine and "from paddle_tpu" not in mine


# --- the yardstick --------------------------------------------------------
def test_the_yardstick_counts_what_issue_33_reckons():
    c = real_config()
    assert yardstick_loop.layer_matrix_params(c) == 51_388_416 - 4 * 2048
    # the block weights four times: 19.7 GB, 24 ms at 819 GB/s at the least
    weights = yardstick_loop.tick_bytes(c, 0, 0) - 2048 * 49152 * 2
    assert round(weights / 1e9, 1) == 19.7
    peak = yardstick.chip_peak("TPU v5 lite")
    assert 24.0 < weights / peak.hbm_bytes_per_s * 1e3 < 24.2
    # a live position costs its K and V over all 192 cache layers
    assert yardstick_loop.tick_bytes(c, 1001, 0) \
        - yardstick_loop.tick_bytes(c, 1, 0) == 1000 * 1_572_864
    # 10 decode rows of a 50 ms tick: both shares under 100 %
    bw = yardstick_loop.hbm_roofline_pct(50.0, c, 2500, 10,
                                         peak.hbm_bytes_per_s)
    mfu = yardstick_loop.mfu_pct(50.0, c, 2500, 10, 10, peak.bf16_flops)
    assert 50 < bw < 70 and 1 < mfu < 10
    assert yardstick_loop.tick_flops(c, 0, 1, 0) == \
        2 * 4 * 48 * yardstick_loop.layer_matrix_params(c)


# --- the readers, on a synthetic trace -------------------------------------
def _op(name, scope, t0, dur):
    return {"name": name, "scope": scope, "start_ns": t0, "dur_ns": dur}


def _run_with(doc, config, facts):
    pt = loader.load_module("layer_metrics", "_program_trace")
    ctx = types.SimpleNamespace(
        trace_doc=doc, config=config,
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    return {"ctx": ctx, "facts": facts, "notes": []}, pt


def _synthetic(scopes):
    """Two whole 50 ms runs of ``jit_tick`` on one device plane, each with
    one operation a scope, 5 ms long, and 10 ms under no scope."""
    ops, runs = [], []
    for r in range(2):
        t0 = r * 60_000_000
        runs.append({"name": "jit_tick(1)", "start_ns": t0,
                     "dur_ns": 50_000_000})
        for i, scope in enumerate(scopes):
            ops.append(_op(f"fusion.{i}", f"jit(tick)/{scope}/dot",
                           t0 + i * 5_000_000, 5_000_000))
        ops.append(_op("copy.1", "jit(tick)/while", t0 + 40_000_000,
                       10_000_000))
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": runs},
        {"name": "XLA Ops", "events": ops}]}]}


def test_the_loop_readers_split_a_tick_by_the_programs_names(monkeypatch):
    scopes = ["blk/qkv", "blk/attn/blk/kv_scatter", "blk/attn",
              "blk/attn_out", "blk/ffn", "loop/exit", "tick/head"]
    doc = _synthetic(scopes)
    facts = {"decode_rows_per_tick": 9.0, "prefill_rows_per_tick": 0.25,
             "prefill_chunk": 32, "live_kv_share": 0.5,
             "ttft_ms": [400.0 + i for i in range(25)],
             "queue_wait_ms": [30.0, 10.0, 20.0],
             "generator_late_ms_max": 1.5}
    run, pt = _run_with(doc, real_config(), facts)
    monkeypatch.setattr(pt, "load", lambda: doc)
    lt = loader.load_module("layer_metrics", "_loop_trace")
    parts = lt.parts_ms(run)
    assert parts["dense"] == pytest.approx(15.0)
    assert parts["attn"] == pytest.approx(10.0)
    assert parts["exit"] == pytest.approx(5.0)
    assert parts["unscoped"] == pytest.approx(10.0)
    read = lambda name: loader.load_module("layer_metrics", name).read(run)
    assert read("loop.dense_ms_per_tick") == pytest.approx(15.0)
    # what the cell reads of prefill and the host, none of it judged
    assert read("sched.ttft_p85_ms") == yardstick.percentile(
        facts["ttft_ms"], 85)
    assert read("sched.queue_wait_p50_ms") == 20.0
    assert read("load.generator_late_ms_max") == 1.5
    assert read("pool.live_kv_pct.chat") == 50.0
    bw = read("loop.tick_hbm_roofline_pct")
    mfu = read("loop.tick_mfu_pct")
    assert 50 < bw < 100 and 0 < mfu < 100


def test_the_loop_readers_find_nothing_in_a_program_without_the_loop(
        monkeypatch):
    """The parent's tick names no ``loop/exit`` and its configuration
    states no loop: every reader returns ``None`` and raises nothing."""
    doc = _synthetic(["blk/qkv", "blk/attn", "blk/ffn", "tick/head"])
    gpt = loader.load_json(loader.root_file(
        "perfbench/configs/gpt3-1.3b-serve.json"))
    run, pt = _run_with(doc, gpt, {
        "decode_rows_per_tick": 9.0, "prefill_rows_per_tick": 0.25,
        "prefill_chunk": 32, "live_kv_share": 0.5})
    monkeypatch.setattr(pt, "load", lambda: doc)
    for m in ("loop.dense_ms_per_tick", "loop.attn_ms_per_tick",
              "loop.exit_ms_per_tick", "loop.unscoped_ms_per_tick",
              "loop.tick_hbm_roofline_pct", "loop.tick_mfu_pct",
              "sched.ttft_p85_ms"):
        assert loader.load_module("layer_metrics", m).read(run) is None, m
    # and with no trace at all
    run["ctx"].trace_doc = None
    assert loader.load_module(
        "layer_metrics", "loop.tick_mfu_pct").read(run) is None


# --- the check, controls included, through check() itself ------------------
@pytest.fixture(scope="module")
def served():
    """A toy engine that served six requests, and what ``check`` is handed:
    the context, the plan and a drive."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import GPT
    from paddle_tpu.serving import ServingConfig, ServingEngine

    fam = loader.load_module("families", "ouro_serve")
    c = toy_config()
    paddle.seed(5)
    net = GPT(fam.model_config(c))
    net.eval()
    e = c["engine"]
    eng = ServingEngine(net, ServingConfig(
        num_slots=e["num_slots"], page_size=e["page_size"],
        pages_per_slot=e["pages_per_slot"], prefix_cache=True))
    rng = np.random.default_rng(9)
    requests = [{"prompt": rng.integers(0, 256, n, dtype=np.int32),
                 "max_new": m, "due_s": 0.0}
                for n, m in ((9, 20), (30, 24), (17, 12), (5, 30), (41, 16),
                             (12, 8))]
    rids = [eng.submit(r["prompt"], r["max_new"]) for r in requests]
    eng.run()
    drive = types.SimpleNamespace(
        eng=eng, rid_of=dict(enumerate(rids)),
        output=lambda i: np.asarray(eng.tokens_so_far(rids[i]), np.int32))
    ctx = types.SimpleNamespace(config=c, seed=2 ** 31 + 3)
    return ctx, eng, {"requests": requests}, drive


def test_the_check_passes_what_the_engine_served(served):
    ctx, eng, plan, drive = served
    chk = loader.load_module("checks", "ouro_serve")
    picked = chk.sample(ctx, plan, list(range(6)))
    assert picked[0] == 4 and len(picked) == len(set(picked)) == 4
    verdict = chk.check(ctx, eng.served_weights(), plan, drive,
                        list(range(6)))
    assert verdict["ok"], verdict["note"]
    assert "allowed" in verdict["note"] and "4 loop steps" in verdict["note"]
    assert not chk.check(ctx, eng.served_weights(), plan, drive, [])["ok"]


@pytest.mark.parametrize("control", ["fp8", "three_steps", "shared_cache",
                                     "unrotated_keys"])
def test_a_control_comes_out_not_correct(served, control):
    ctx, eng, plan, drive = served
    chk = loader.load_module("checks", "ouro_serve")
    verdict = chk.check(ctx, eng.served_weights(), plan, drive,
                        list(range(6)), control=control)
    assert not verdict["ok"], verdict["note"]
    assert f"[{control}]" in verdict["note"]


# --- the cell, rehearsed on the CPU ----------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory, bench):
    dst = tmp_path_factory.mktemp("checkout_ouro")
    shutil.copytree(os.path.join(loader.ROOT, "perfbench"),
                    dst / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("configs", "traffic"):
        for f in os.listdir(os.path.join(TOY, kind)):
            target = dst / "perfbench" / kind / f
            assert not target.exists()
            shutil.copy(os.path.join(TOY, kind, f), target)
    bench = json.loads(json.dumps(bench))
    add = loader.load_json(os.path.join(TOY, "benchmark_entries.json"))
    bench["configs"] += add["configs"]
    bench["workloads"] += add["workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-ouro-cell")
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def rehearse(copy, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(copy), loader.ROOT])}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_rehearsal.py"), "1",
         "--workload", "toy-ouro-cell", "--seed", str(2 ** 31 + 11),
         "--seconds", "1.5", "--trace", str(trace)],
        cwd=copy, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    return json.loads(lines[-1]), p.stdout


def test_the_cell_rehearses_end_to_end_on_the_cpu(copy):
    line, out = rehearse(copy, 0)
    assert line["correct"] is True, out[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"      # a rehearsal, no number
    # the time to first token is read (the notes) and not judged: six to
    # eight ticks of 56 ms, it spreads by the tick's phase alone (PERF.md)
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert "ttft p50 / p85 / p90" in out
    assert "through 4 loop steps" in out and "mean expected exit step" in out


def test_the_traced_rehearsal_reads_what_a_cpu_run_can(copy):
    """No device in a CPU trace: the ``*_ms_per_tick`` readers and the two
    shares return nothing and are left out; the program's counter and the
    host's numbers are there."""
    line, out = rehearse(copy, 1)
    assert line["correct"] is True, out[-2000:]
    host = {"sched.ttft_p85_ms", "sched.queue_wait_p50_ms",
            "load.generator_late_ms_max", "pool.live_kv_pct.chat"}
    assert set(line["metrics"]) >= {"proc.compiles_in_window"} | host
    assert not {m for m in line["metrics"] if m.startswith("loop.")}
    assert line["metrics"]["proc.compiles_in_window"]["value"] == 0
    assert line["metrics"]["sched.ttft_p85_ms"]["value"] > 0
    assert 0 < line["metrics"]["pool.live_kv_pct.chat"]["value"] <= 100
