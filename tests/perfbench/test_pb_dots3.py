"""The latent-attention model's cell: the configuration file against the
catalog's row and its family's ``check_widths``, the toy family through the
contract's rules, ``yardstick_mla``'s counts against hand arithmetic, the
new readers on a synthetic trace, the check and its controls through
``check()`` itself at a small size, and a CPU rehearsal of the cell on a toy
configuration in a temporary copy."""
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import loader, yardstick, yardstick_mla

from test_pb_contract import config_file_is_sound, family_is_only_a_model

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_dots3")
CELL = "serve-dots3-longdoc-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("served.tick_device_ms_p50", "served.dense_ms_per_tick",
       "served.head_sample_ms_per_tick", "served.unscoped_ms_per_tick",
       "latent.scatter_ms_per_tick", "dsa.index_ms_per_tick",
       "dsa.select_ms_per_tick", "mla.attn_ms_per_tick",
       "swa.attn_ms_per_tick", "moe.tick_route_ms_per_tick",
       "moe.tick_experts_ms_per_tick", "moe.tick_shared_ms_per_tick",
       "dsa.index_roofline_pct", "mla.attn_roofline_pct",
       "swa.attn_roofline_pct", "moe.tick_experts_hbm_roofline_pct",
       "served.tick_hbm_roofline_pct", "served.tick_mfu_pct",
       "dsa.selected_share_pct", "pool.live_latent_pct",
       "pool.window_pages_freed_per_tick",
       "moe.tick_expert_load_max_over_mean", "moe.tick_experts_touched_pct",
       "served.prefill_tokens_per_tick",
       "served.decode_rows_per_tick",
       "served.tokens_per_s_slice_p50",
       "served.host_ms_per_tick")
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "q_lora_rank", "kv_lora_rank",
          "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
          "swa_num_attention_heads", "swa_q_lora_rank", "swa_kv_lora_rank",
          "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
          "sliding_window_size", "index_n_heads", "index_head_dim",
          "index_topk", "num_experts_per_tok")


def real_config():
    return loader.load_json(loader.root_file(
        "perfbench/configs/dots3-note-prev-serve.json"))


def toy_config():
    return loader.load_json(os.path.join(TOY, "configs", "toy-dots3.json"))


@pytest.fixture(scope="module")
def bench():
    return loader.load_json(loader.root_file("BENCHMARK.json"))


# --- the configuration -----------------------------------------------------
def test_the_configuration_is_the_catalogs_row_cut_in_three_keys(bench):
    entry = next(c for c in bench["configs"]
                 if c["name"] == "dots3-note-prev-serve")
    cfg = real_config()
    config_file_is_sound(entry, cfg)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "dots3-note-prev")
        assert entry["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value
            elif key == "layer_types":
                assert cfg[key] == value[:5]
            else:
                assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 32, 19008)
    assert cfg["published"]["chips_a_layer"] * cfg["n_routed_experts"] == 256
    for key in ("apply_mla_qkv_lora_rescale", "attention_gate", "indexer",
                "router", "select_bias_range", "window"):
        assert key in cfg["assumed"], key
    e = cfg["engine"]
    assert e["page_size"] * e["pages_per_slot"] == 33792
    assert not e["prefix_cache"] and e["num_slots"] >= 12
    # the pools' bytes, as ISSUE 37 reckons them: 1,408 B a token a full layer
    token = 2 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
                 + cfg["index_head_dim"])
    assert token == 1408
    assert round(2 * e["num_slots"] * 33792 * token / 1e9, 2) == \
        {12: 1.14, 13: 1.24, 14: 1.33}.get(e["num_slots"], 0)


@pytest.mark.parametrize("key", WIDTHS)
def test_a_changed_width_is_refused_by_its_key(bench, key):
    entry = next(c for c in bench["configs"]
                 if c["name"] == "dots3-note-prev-serve")
    cfg = real_config()
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        config_file_is_sound(entry, {**cfg, key: cfg[key] * 2})


@pytest.mark.parametrize("key,value", [
    ("layer_types", ["full_attention"] * 4), ("n_routed_experts", 48),
    ("vocab_size", 20000), ("experts_held_first", 16)])
def test_a_cut_that_is_no_whole_share_is_refused(bench, key, value):
    entry = next(c for c in bench["configs"]
                 if c["name"] == "dots3-note-prev-serve")
    with pytest.raises(ValueError, match=key if key != "experts_held_first"
                       else "n_routed_experts"):
        config_file_is_sound(entry, {**real_config(), key: value})


def test_the_family_builds_the_model_from_the_files_sizes():
    from paddle_tpu.models.dots3 import Dots3Config

    fam = loader.load_module("families", "dots3_serve")
    cfg = fam.model_config(real_config())
    assert isinstance(cfg, Dots3Config)
    assert cfg.experts_held == (0, 32) and cfg.n_routed_experts == 256
    assert round(cfg.num_params() / 1e9, 3) == 4.087
    assert cfg.select_bias_range == 0.02 and cfg.vocab_size == 19008
    assert cfg.layer_types == ("full_attention",) * 2 \
        + ("sliding_attention",) * 3
    with pytest.raises(ValueError, match="sigmoid"):
        fam.model_config({**real_config(), "scoring_func": "softmax"})
    assert fam.limits(real_config()) == {
        "vocab_size": 19008, "num_slots": 12, "capacity": 33792}
    # the yardstick counts the same model
    assert yardstick_mla.total_params(real_config()) == cfg.num_params() - (
        5 * 2 * 5120 + 2 * (1024 + 512) + 3 * 2048 + 2 * 2 * 128
        + 4 * 256 + 5120)


def test_the_traffic_is_issue_37s_and_fits_a_slot():
    gen = loader.load_module("generators", "closed_backlog")
    fam = loader.load_module("families", "dots3_serve")
    params = loader.load_data("traffic", "longdoc-backlog")
    assert params["prompt"] == {"median": 16384, "sigma": 0.35,
                                "lo": 8192, "hi": 32768}
    assert params["output"] == {"median": 384, "sigma": 0.5, "lo": 96,
                                "hi": 1024}
    assert (params["warm_in_s"], params["slices"], params["traced_s"],
            params["order_seed"]) == (15.0, 9, 4.0, 20260927)
    assert params["cycle"] == 2 and params["requests"] == 400
    small = dict(params, requests=20, cycle=10)
    plan = gen.generate(small, 2 ** 31 + 5, 45.0, fam.limits(real_config()))
    sizes = [(len(r["prompt"]), r["max_new"]) for r in plan["requests"]]
    assert max(p + n for p, n in sizes) <= 33792 + 1
    assert min(p for p, _ in sizes) >= 8192
    # as the file stands the queue alternates the distributions' quartiles,
    # whichever the seed puts first
    for seed in (7, 8, 2 ** 31 + 9):
        plan = gen.generate(dict(params, requests=4), seed, 45.0,
                            fam.limits(real_config()))
        assert sorted((len(r["prompt"]), r["max_new"])
                      for r in plan["requests"]) == [
            (12939, 274)] * 2 + [(20746, 538)] * 2
    assert max(int(r["prompt"].max()) for r in plan["requests"]) < 19008
    assert max(int(r["prompt"].max()) for r in plan["requests"]) > 18000


def test_the_benchmarks_reference_is_the_programs_copy():
    def body(path):
        with open(loader.root_file(path), encoding="utf-8") as f:
            text = f.read()
        return text[text.index("With ``N(.)``"):]

    mine = body("perfbench/references/dots3.py")
    assert mine == body("paddle_tpu/models/dots3_reference.py")
    assert "import paddle_tpu" not in mine and "from paddle_tpu" not in mine


# --- the toy family, through the contract's rules ---------------------------
@pytest.fixture
def with_toy(tmp_path):
    dst, real = str(tmp_path / "perfbench"), loader.HERE
    shutil.copytree(real, dst, ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("families", "checks"):
        shutil.copy(os.path.join(TOY, kind, "toy_dots3.py"),
                    os.path.join(dst, kind, "toy_dots3.py"))
    loader.HERE = dst
    try:
        yield dst
    finally:
        loader.HERE = real
        for name, mod in list(sys.modules.items()):
            if name.startswith("perfbench.") and (getattr(
                    mod, "__file__", None) or "").startswith(dst):
                del sys.modules[name]


def test_the_toy_family_is_a_family_and_takes_its_own_file(with_toy):
    family_is_only_a_model(os.path.join(with_toy, "families",
                                        "toy_dots3.py"))
    family_is_only_a_model(os.path.join(with_toy, "families",
                                        "dots3_serve.py"))
    cfg = toy_config()
    config_file_is_sound({"name": "toy-dots3", "reduced": cfg["reduced"]},
                         cfg)
    real = loader.load_module("families", "dots3_serve")
    with pytest.raises(ValueError, match="hidden_size"):
        real.check_widths(cfg)          # the shipped family holds to 5,120


@pytest.mark.parametrize("key", WIDTHS)
def test_the_toy_family_refuses_each_changed_width_by_name(with_toy, key):
    cfg = toy_config()
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        config_file_is_sound(
            {"name": "toy-dots3", "reduced": cfg["reduced"]},
            {**cfg, key: cfg[key] * 2})


# --- the yardstick, against hand arithmetic --------------------------------
def test_the_yardstick_counts_what_issue_37_reckons():
    c = real_config()
    m = lambda n: round(n / 1e6, 1)                          # noqa: E731
    assert m(yardstick_mla.attention_params(c, "full_attention")) == 144.0
    assert m(yardstick_mla.attention_params(c, "sliding_attention")) == 90.8
    assert m(yardstick_mla.expert_params(c)) == 23.6
    assert m(yardstick_mla.held_params(c) / 4) == 755.0
    assert round(yardstick_mla.total_params(c) / 1e9, 3) == 4.087
    peak = yardstick.chip_peak("TPU v5 lite")
    # one read of the weights: 8.2 GB, 10 ms at 819 GB/s at the least
    weights = yardstick_mla.total_params(c) * 2
    assert 9.9 < weights / peak.hbm_bytes_per_s * 1e3 < 10.1
    # the indexer: 268 queries x 16,000 keys x 64 x 128 x 2, two layers
    ops, moved = yardstick_mla.index_ops_bytes(c, 12, 1, 256, 16000)
    assert ops == 2 * 2.0 * 268 * 16000 * 64 * 128
    assert moved == 2 * (13 * 16000 * 128 * 2 + 268 * 64 * 128 * 2)
    # attention: 2,048 latents of 576 a query, 128 heads, scored and weighed
    ops, moved = yardstick_mla.mla_ops_bytes(c, 12, 1, 256, 16000)
    assert ops == 2 * 2.0 * 268 * 128 * 2048 * (576 + 512)
    assert moved == 2 * 268 * 2048 * 576 * 2
    assert yardstick_mla.mla_ops_bytes(c, 12, 0, 256, 100)[1] \
        == 2 * 12 * 100 * 576 * 2                    # fewer than 2,048 seen
    ops, moved = yardstick_mla.swa_ops_bytes(c, 12, 1, 256, 16000)
    assert ops == 3 * 2.0 * 268 * 64 * 513 * (1088 + 1024)
    assert moved == 3 * (12 * 513 + 513 + 255) * 1088 * 2
    # experts: half of them touched is half of 6.04 GB
    assert round(yardstick_mla.experts_bytes(c, 0.5) / 1e9, 2) == 3.02
    tick = yardstick_mla.tick_bytes(c, 12, 1, 256, 16000, 12, 1.0)
    assert 8.2e9 < tick < 9.5e9
    flops = yardstick_mla.tick_flops(c, 12, 1, 256, 16000, 12, 268)
    assert 0.8e12 < flops < 1.6e12
    # a tick of 60 ms: both shares well under 100 %
    assert 100 * tick / peak.hbm_bytes_per_s / 0.060 < 25
    assert 100 * flops / 0.060 / peak.bf16_flops < 15


# --- the readers, on a synthetic trace --------------------------------------
def _op(name, scope, t0, dur):
    return {"name": name, "scope": scope, "start_ns": t0, "dur_ns": dur}


def _synthetic(scopes, kernels=()):
    """Two whole 60 ms runs of ``jit_tick`` on one device plane, each with
    one operation a scope, 2 ms long, a grouped-matmul kernel of 4 ms and
    6 ms under no scope."""
    ops, runs = [], []
    for r in range(2):
        t0 = r * 70_000_000
        runs.append({"name": "jit_tick(1)", "start_ns": t0,
                     "dur_ns": 60_000_000})
        for i, scope in enumerate(scopes):
            ops.append(_op(f"fusion.{i}", f"jit(tick)/while/body/{scope}/dot",
                           t0 + i * 2_000_000, 2_000_000))
        for j, name in enumerate(kernels):
            ops.append(_op(name, "", t0 + 40_000_000 + j * 4_000_000,
                           4_000_000))
        ops.append(_op("copy.1", "jit(tick)/while", t0 + 50_000_000,
                       6_000_000))
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": runs},
        {"name": "XLA Ops", "events": ops}]}]}


def _run_with(doc, config, facts):
    pt = loader.load_module("layer_metrics", "_program_trace")
    ctx = types.SimpleNamespace(
        trace_doc=doc, config=config,
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    return {"ctx": ctx, "facts": facts, "notes": []}, pt


FACTS = {"decode_rows_per_tick": 11.0, "prefill_rows_per_tick": 1.0,
         "prefill_chunk": 256, "live_kv_share": 0.5,
         "serve_tokens_per_s_slice_p50": 4000.0,
         "tick_selected_share": 0.2, "tick_expert_rows": 270.0,
         "tick_expert_load_max_over_mean": 2.5,
         "tick_experts_touched_share": 0.9,
         "window_pages_freed_per_tick": 2.1}
SCOPES = ["blk/qkv", "blk/latent_scatter", "blk/index", "blk/select",
          "blk/attn/mla", "blk/attn/swa", "blk/attn_out",
          "blk/ffn/moe/route", "blk/ffn/moe/dispatch",
          "blk/ffn/moe/experts", "blk/ffn/moe/combine",
          "blk/ffn/moe/shared", "blk/ffn", "tick/embed", "tick/head",
          "tick/sample"]


def test_the_readers_split_a_tick_by_the_programs_names(monkeypatch):
    doc = _synthetic(SCOPES, kernels=("%moe_gmm.3 = custom-call",))
    run, pt = _run_with(doc, real_config(), dict(FACTS))
    monkeypatch.setattr(pt, "load", lambda: doc)
    read = lambda name: loader.load_module("layer_metrics", name).read(run)
    want = {"served.tick_device_ms_p50": 60.0,
            "served.dense_ms_per_tick": 6.0,
            "served.head_sample_ms_per_tick": 6.0,
            "latent.scatter_ms_per_tick": 2.0, "dsa.index_ms_per_tick": 2.0,
            "dsa.select_ms_per_tick": 2.0, "mla.attn_ms_per_tick": 2.0,
            "swa.attn_ms_per_tick": 2.0, "moe.tick_route_ms_per_tick": 2.0,
            "moe.tick_experts_ms_per_tick": 10.0,
            "moe.tick_shared_ms_per_tick": 2.0,
            "dsa.selected_share_pct": 20.0,
            "pool.live_latent_pct": 50.0,
            "pool.window_pages_freed_per_tick": 2.1,
            "moe.tick_expert_load_max_over_mean": 2.5,
            "moe.tick_experts_touched_pct": 90.0,
            "served.prefill_tokens_per_tick": 256.0,
            "served.decode_rows_per_tick": 11.0,
            "served.tokens_per_s_slice_p50": 4000.0}
    for name, value in want.items():
        assert read(name) == pytest.approx(value), name
    # the parts and what no name covers add up to the tick
    named = sum(read(n) for n in NEW[1:12]
                if n != "served.unscoped_ms_per_tick")
    assert named + read("served.unscoped_ms_per_tick") == pytest.approx(60.0)
    for name in NEW[12:18]:
        assert 0 < read(name) < 100, name
    assert sorted(NEW) == sorted(
        f[:-3] for f in os.listdir(os.path.join(loader.HERE,
                                                "layer_metrics"))
        if f[:-3] in NEW)


def test_the_readers_find_nothing_in_a_program_without_the_model(
        monkeypatch):
    """The parent's tick names no ``blk/attn/mla`` and its configuration
    states no indexer: every trace reader returns ``None`` and raises
    nothing; so with no trace at all."""
    doc = _synthetic(["blk/qkv", "blk/attn", "blk/ffn", "tick/head"])
    gpt = loader.load_json(loader.root_file(
        "perfbench/configs/gpt3-1.3b-serve.json"))
    run, pt = _run_with(doc, gpt, {
        "decode_rows_per_tick": 9.0, "prefill_rows_per_tick": 0.25,
        "prefill_chunk": 32, "live_kv_share": 0.5})
    monkeypatch.setattr(pt, "load", lambda: doc)
    for name in NEW[:18] + NEW[18:19] + NEW[20:23]:
        assert loader.load_module("layer_metrics", name).read(run) is None, \
            name
    run["ctx"].trace_doc = None
    assert loader.load_module(
        "layer_metrics", "served.tick_mfu_pct").read(run) is None


def test_the_cells_lists_name_the_new_metrics(bench):
    cell = loader.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] \
                and m["moves"] == "serve_tokens_per_s"


# --- the check, controls included, through check() itself -------------------
@pytest.fixture(scope="module")
def served():
    """A toy engine that served five requests, and what ``check`` is
    handed: the context, the plan and a drive."""
    import paddle_tpu as paddle
    from paddle_tpu.models.dots3 import Dots3
    from paddle_tpu.serving import ServingConfig, ServingEngine

    sys.path.insert(0, os.path.join(TOY, "families"))
    fam = loader.load_module("families", "dots3_serve")
    toy = loader.load_json(os.path.join(TOY, "configs", "toy-dots3.json"))
    widths = {k: toy[k] for k in fam.PUBLISHED}
    c = dict(toy, family="dots3_serve")
    paddle.seed(5)
    net = Dots3(fam.model_config(c, widths))
    net.eval()
    e = c["engine"]
    eng = ServingEngine(net, ServingConfig(
        num_slots=e["num_slots"], page_size=e["page_size"],
        pages_per_slot=e["pages_per_slot"], prefix_cache=False))
    rng = np.random.default_rng(9)
    requests = [{"prompt": rng.integers(0, 96, n, dtype=np.int32),
                 "max_new": m, "due_s": 0.0}
                for n, m in ((19, 20), (50, 24), (27, 12), (15, 30),
                             (41, 16))]
    rids = [eng.submit(r["prompt"], r["max_new"]) for r in requests]
    eng.run()
    drive = types.SimpleNamespace(
        eng=eng, rid_of=dict(enumerate(rids)),
        output=lambda i: np.asarray(eng.tokens_so_far(rids[i]), np.int32))
    ctx = types.SimpleNamespace(config=c, seed=2 ** 31 + 3)
    return ctx, eng, {"requests": requests}, drive


#: the fixture serves float32, which the reference repeats but for the
#: order of its sums: the shipped limits are bf16's at the published widths
FLOAT32_LIMITS = (0.1, 0.05, 0.05, 0.05, 0.05)


def test_the_check_passes_what_the_engine_served(served):
    ctx, eng, plan, drive = served
    chk = loader.load_module("checks", "dots3_serve")
    picked = chk.sample(ctx, plan, drive, list(range(5)))
    assert picked[0] == 1 and len(picked) == len(set(picked)) == chk.SAMPLE
    # only requests the engine kept a record of are sampled
    watched = types.SimpleNamespace(
        eng=types.SimpleNamespace(tick_record=types.SimpleNamespace(
            has=lambda rid: rid in (0, 3))), rid_of=drive.rid_of)
    assert sorted(chk.sample(ctx, plan, watched, list(range(5)))) == [0, 3]
    assert chk.sample(ctx, plan, watched, [1, 2]) == []
    verdict = chk.check(ctx, eng.served_weights(), plan, drive,
                        list(range(5)), limits=FLOAT32_LIMITS)
    assert verdict["ok"], verdict["note"]
    assert verdict["note"].count("allowed") == 6
    assert not chk.check(ctx, eng.served_weights(), plan, drive, [])["ok"]


@pytest.mark.parametrize("control", [
    "fp8", "recent_topk", "window_all", "no_gate", "unscaled_latent",
    "other_share", "no_select_bias"])
def test_a_control_comes_out_not_correct(served, control):
    ctx, eng, plan, drive = served
    chk = loader.load_module("checks", "dots3_serve")
    verdict = chk.check(ctx, eng.served_weights(), plan, drive,
                        list(range(5)), control=control,
                        limits=FLOAT32_LIMITS)
    assert not verdict["ok"], verdict["note"]
    assert f"[{control}]" in verdict["note"]


# --- the cell, rehearsed on the CPU ------------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory, bench):
    dst = tmp_path_factory.mktemp("checkout_dots3")
    shutil.copytree(os.path.join(loader.ROOT, "perfbench"),
                    dst / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("configs", "traffic", "families", "checks"):
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
            m["workloads"].append("toy-dots3-cell")
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def rehearse(copy, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(copy), loader.ROOT])}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_rehearsal.py"), "1",
         "--workload", "toy-dots3-cell", "--seed", str(2 ** 31 + 11),
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
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "selected sets" in out


def test_a_traced_rehearsal_reports_what_the_cpu_can(copy):
    """The CPU's trace has no device plane: the device readers return
    nothing, the counters and the scheduler's readers report."""
    line, out = rehearse(copy, 1)
    assert line["correct"] is True, out[-2000:]
    got = set(line["metrics"])
    assert {"dsa.selected_share_pct", "pool.live_latent_pct",
            "pool.window_pages_freed_per_tick",
            "moe.tick_expert_load_max_over_mean",
            "moe.tick_experts_touched_pct",
            "served.prefill_tokens_per_tick",
            "served.decode_rows_per_tick",
            "served.tokens_per_s_slice_p50"} <= got
    assert line["metrics"]["pool.window_pages_freed_per_tick"]["value"] > 0
    assert 0 < line["metrics"]["dsa.selected_share_pct"]["value"] < 100
