"""DeepSeek-V2's cell: the configuration file against the catalog's row and
its family's ``check_widths``, the toy family through the contract's rules,
``yardstick_mla_dense``'s counts against a hand-worked call, the new readers
on a synthetic trace, the check and its controls through ``check()`` itself
at a small size, and a CPU rehearsal of the cell on a toy configuration in a
temporary copy."""
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import loader, yardstick, yardstick_mla_dense as ymd

from test_pb_contract import BACKLOG_HOLDS as HOLDS, config_file_is_sound, \
    family_is_only_a_model

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_dsv2")
CELL = "serve-dsv2-docqa-backlog"
CONFIG = "deepseek-v2-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("served.tick_device_ms_p50", "served.dense_ms_per_tick",
       "served.head_sample_ms_per_tick", "served.unscoped_ms_per_tick",
       "latent.scatter_ms_per_tick", "mla.dense_chunk_ms_per_tick",
       "mla.dense_decode_ms_per_tick", "moe.tick_route_ms_per_tick",
       "moe.tick_experts_ms_per_tick",
       "moe.tick_shared_ms_per_tick",
       "mla.dense_attn_roofline_pct",
       "moe.tick_experts_hbm_roofline_pct",
       "served.tick_hbm_roofline_pct", "served.tick_mfu_pct",
       "moe.tick_group_hit_pct", "moe.tick_expert_load_max_over_mean",
       "moe.tick_experts_touched_pct", "pool.live_latent_pct",
       "served.prefill_tokens_per_tick",
       "served.decode_rows_per_tick",
       "served.tokens_per_s_slice_p50",
       "served.host_ms_per_tick") + HOLDS    # this cell's since PR 53
#: the entries that list this cell alone: its own mechanism's (the decode
#: rows' time and the group hits are Ling's cell's too since PR 53)
OWN = ("mla.dense_chunk_ms_per_tick", "mla.dense_attn_roofline_pct")
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "q_lora_rank", "kv_lora_rank",
          "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
          "num_experts_per_tok", "n_shared_experts", "n_group", "topk_group",
          "routed_scaling_factor", "max_position_embeddings")


def real_config():
    return loader.load_json(loader.root_file(
        "perfbench/configs/deepseek-v2-serve.json"))


def toy_config():
    return loader.load_json(os.path.join(TOY, "configs", "toy-dsv2.json"))


@pytest.fixture(scope="module")
def bench():
    return loader.load_json(loader.root_file("BENCHMARK.json"))


# --- the configuration -----------------------------------------------------
def test_the_configuration_is_the_catalogs_row_cut_in_three_keys(bench):
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = real_config()
    config_file_is_sound(entry, cfg)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "DeepSeek-V2")
        assert entry["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value
            else:
                assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 20, 12800)
    assert cfg["published"]["chips_a_layer"] * cfg["n_routed_experts"] == 160
    assert cfg["published"]["chips_a_layer"] == cfg["n_group"]
    for key in ("rope", "initializer_range", "group_score", "router",
                "shared"):
        assert key in cfg["assumed"], key
    assert "8 chips" in cfg["deployment"] and cfg["memory"]
    e = cfg["engine"]
    assert e["page_size"] * e["pages_per_slot"] == 11264
    assert not e["prefix_cache"] and e["num_slots"] == 20
    # the cache's bytes, as ISSUE 40 reckons them: 1,152 B a token a layer
    assert ymd.latent_row_bytes(cfg) == 1152
    assert round(5 * e["num_slots"] * 11264 * 1152 / 1e9, 2) == 1.30


@pytest.mark.parametrize("key", WIDTHS)
def test_a_changed_width_is_refused_by_its_key(bench, key):
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = real_config()
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        config_file_is_sound(entry, {**cfg, key: cfg[key] * 2})


def test_a_changed_yarn_block_is_refused_by_its_key(bench):
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = real_config()
    for change in ({"factor": 20}, {"mscale": 1.0},
                   {"original_max_position_embeddings": 8192}):
        with pytest.raises(ValueError, match=r"\brope_scaling\b"):
            config_file_is_sound(entry, {**cfg, "rope_scaling": {
                **cfg["rope_scaling"], **change}})


@pytest.mark.parametrize("key,value", [
    ("n_routed_experts", 30), ("n_routed_experts", 10),
    ("vocab_size", 13000), ("experts_held_first", 10),
    ("num_hidden_layers", 1)])
def test_a_cut_that_is_no_whole_share_is_refused(bench, key, value):
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    with pytest.raises(ValueError, match=key if key != "experts_held_first"
                       else "n_routed_experts"):
        config_file_is_sound(entry, {**real_config(), key: value})


def test_the_family_builds_the_model_from_the_files_sizes():
    from paddle_tpu.models.deepseek_v2 import DeepseekV2Config

    fam = loader.load_module("families", "deepseek_v2_serve")
    cfg = fam.model_config(real_config())
    assert isinstance(cfg, DeepseekV2Config)
    assert cfg.experts_held == (0, 20) and cfg.n_routed_experts == 160
    assert round(cfg.num_params() / 1e9, 3) == 3.145
    assert cfg.vocab_size == 12800 and cfg.num_hidden_layers == 5
    assert cfg.rope_scaling["factor"] == 40
    assert round(cfg.softmax_scale, 5) == 0.11472
    with pytest.raises(ValueError, match="softmax"):
        fam.model_config({**real_config(), "scoring_func": "sigmoid"})
    assert fam.limits(real_config()) == {
        "vocab_size": 12800, "num_slots": 20, "capacity": 11264}
    assert fam.PREFILL_CHUNKS_PER_TICK in (1, 2, 4)
    # the yardstick counts the same model (it leaves the norms out)
    assert ymd.total_params(real_config()) == cfg.num_params() - (
        5 * 2 * 5120 + 5 * (1536 + 512) + 5120)


def test_the_traffic_is_issue_40s_and_fits_a_slot():
    gen = loader.load_module("generators", "closed_backlog")
    fam = loader.load_module("families", "deepseek_v2_serve")
    params = loader.load_data("traffic", "docqa-8k-backlog")
    assert params["prompt"] == {"median": 8192, "sigma": 0.35,
                                "lo": 4096, "hi": 16384}
    assert params["output"] == {"median": 192, "sigma": 0.5, "lo": 48,
                                "hi": 768}
    assert (params["warm_in_s"], params["slices"], params["traced_s"],
            params["order_seed"]) == (15.0, 9, 4.0, 20260930)
    assert params["cycle"] in (2,) and params["requests"] >= 600
    lim = fam.limits(real_config())
    plan = gen.generate(dict(params, requests=20, cycle=10), 2 ** 31 + 5,
                        45.0, lim)
    sizes = [(len(r["prompt"]), r["max_new"]) for r in plan["requests"]]
    assert max(p + n for p, n in sizes) <= 11264 + 1
    # every prompt is past YaRN's original 4,096 positions
    assert min(p for p, _ in sizes) >= 4096
    for seed in (7, 8, 2 ** 31 + 9):
        plan = gen.generate(dict(params, requests=4), seed, 45.0, lim)
        assert sorted((len(r["prompt"]), r["max_new"])
                      for r in plan["requests"]) == [
            (6469, 137)] * 2 + [(10373, 269)] * 2
    assert max(int(r["prompt"].max()) for r in plan["requests"]) < 12800
    assert max(int(r["prompt"].max()) for r in plan["requests"]) > 12000


def test_the_benchmarks_reference_is_the_programs_copy():
    def body(path):
        with open(loader.root_file(path), encoding="utf-8") as f:
            text = f.read()
        return text[text.index("With ``N(.)``"):]

    mine = body("perfbench/references/deepseek_v2.py")
    assert mine == body("paddle_tpu/models/deepseek_v2_reference.py")
    assert "import paddle_tpu" not in mine and "from paddle_tpu" not in mine


# --- the toy family, through the contract's rules ---------------------------
@pytest.fixture
def with_toy(tmp_path):
    dst, real = str(tmp_path / "perfbench"), loader.HERE
    shutil.copytree(real, dst, ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("families", "checks"):
        shutil.copy(os.path.join(TOY, kind, "toy_dsv2.py"),
                    os.path.join(dst, kind, "toy_dsv2.py"))
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
    family_is_only_a_model(os.path.join(with_toy, "families", "toy_dsv2.py"))
    family_is_only_a_model(os.path.join(with_toy, "families",
                                        "deepseek_v2_serve.py"))
    cfg = toy_config()
    config_file_is_sound({"name": "toy-dsv2", "reduced": cfg["reduced"]},
                         cfg)
    real = loader.load_module("families", "deepseek_v2_serve")
    with pytest.raises(ValueError, match="hidden_size"):
        real.check_widths(cfg)          # the shipped family holds to 5,120


@pytest.mark.parametrize("key", WIDTHS)
def test_the_toy_family_refuses_each_changed_width_by_name(with_toy, key):
    cfg = toy_config()
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        config_file_is_sound(
            {"name": "toy-dsv2", "reduced": cfg["reduced"]},
            {**cfg, key: cfg[key] * 2})


# --- the yardstick, against hand arithmetic --------------------------------
def test_the_yardstick_counts_what_issue_40_reckons():
    c = real_config()
    m = lambda n: round(n / 1e6, 1)                          # noqa: E731
    assert m(ymd.attention_params(c)) == 149.2
    assert m(ymd.expert_params(c)) == 23.6
    assert m(ymd.held_params(c) / 4) == 471.9
    assert m(ymd.dense_params(c)) == 1126.9   # 5 x 149.2 + 188.7 + 4 x 48.0
    assert round(ymd.total_params(c) / 1e9, 3) == 3.145
    peak = yardstick.chip_peak("TPU v5 lite")
    # one read of the weights: 6.29 GB, 7.7 ms at 819 GB/s at the least
    assert 7.6 < ymd.total_params(c) * 2 / peak.hbm_bytes_per_s * 1e3 < 7.8


def test_the_yardstick_on_a_hand_worked_call():
    c = real_config()
    peak = yardstick.chip_peak("TPU v5 lite")
    # a chunk of 256 queries behind 10,240 positions: query i sees 10,241 + i
    pairs = sum(10241 + i for i in range(256))
    keys = 10240 + 256
    assert pairs == 256 * 10240 + 256 * 257 // 2
    ops, moved = ymd.call_ops_bytes(c, pairs, keys)
    absorbed = 2 * 128 * (576 + 512) * pairs
    expanded = 2 * 128 * (192 + 128) * pairs + 2 * 512 * 128 * 256 * keys
    assert round(absorbed / 1e12, 2) == 0.74        # ISSUE 40's 0.73 TFLOP
    assert ops == expanded < absorbed
    assert moved == keys * 1152
    # compute-bound: the lesser form at the chip's peak
    assert ymd.least_ms(ops, moved, peak) == pytest.approx(
        expanded / peak.bf16_flops * 1e3)
    # twelve decode rows of one query behind 8,000: absorbed is the lesser
    # (the expanded form would expand every key for one query), and the
    # call sits at the chip's ridge, 242 FLOP a byte against 240
    ops, moved = ymd.call_ops_bytes(c, 12 * 8001, 12 * 8001)
    assert ops == 2 * 128 * 1088 * 12 * 8001
    assert round(ops / moved) == 242
    assert round(peak.bf16_flops / peak.hbm_bytes_per_s) in range(236, 245)
    # a chunk narrower than ~170 queries is cheaper absorbed
    narrow = sum(4097 + i for i in range(128))
    assert ymd.call_ops_bytes(c, narrow, 4096 + 128)[0] \
        == 2 * 128 * 1088 * narrow
    # the whole tick: five layers of both calls, and the weights once
    calls = ((12 * 8001, 12 * 8001), (2 * pairs, keys))
    assert ymd.attention_least_ms(c, calls, peak) == pytest.approx(
        5 * sum(ymd.least_ms(*ymd.call_ops_bytes(c, p, k), peak)
                for p, k in calls))
    tick = ymd.tick_bytes(c, 524, calls, 12, 1.0)
    assert 6.2e9 < tick < 7.2e9
    flops = ymd.tick_flops(c, 524, calls, 12, 4 * 6 * 524 * 3 / 8)
    assert 4e12 < flops < 8e12


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


FACTS = {"decode_rows_per_tick": 12.0, "prefill_rows_per_tick": 2.0,
         "prefill_chunk": 256, "live_kv_share": 0.5,
         "serve_tokens_per_s_slice_p50": 12000.0,
         "tick_group_hit_share": 0.375, "tick_expert_rows": 390.0,
         "tick_expert_load_max_over_mean": 1.8,
         "tick_experts_touched_share": 0.95,
         "tick_decode_pairs": 12 * 8001.0, "tick_decode_keys": 12 * 8001.0,
         "tick_chunk_pairs": 512 * 4500.0, "tick_chunk_keys": 4756.0}
SCOPES = ["blk/qkv", "blk/latent_scatter", "blk/attn/mla_chunk",
          "blk/attn/mla_decode", "blk/attn_out", "blk/ffn/moe/route",
          "blk/ffn/moe/dispatch", "blk/ffn/moe/experts",
          "blk/ffn/moe/combine", "blk/ffn/moe/shared", "blk/ffn",
          "tick/embed", "tick/head", "tick/sample"]


def test_the_readers_split_a_tick_by_the_programs_names(monkeypatch):
    doc = _synthetic(SCOPES, kernels=("%moe_gmm.3 = custom-call",))
    run, pt = _run_with(doc, real_config(), dict(FACTS))
    monkeypatch.setattr(pt, "load", lambda: doc)
    read = lambda name: loader.load_module("layer_metrics", name).read(run)
    want = {"served.tick_device_ms_p50": 60.0,
            "served.dense_ms_per_tick": 6.0,
            "served.head_sample_ms_per_tick": 6.0,
            "latent.scatter_ms_per_tick": 2.0,
            "mla.dense_chunk_ms_per_tick": 2.0,
            "mla.dense_decode_ms_per_tick": 2.0,
            "moe.tick_route_ms_per_tick": 2.0,
            "moe.tick_experts_ms_per_tick": 10.0,
            "moe.tick_shared_ms_per_tick": 2.0,
            "moe.tick_group_hit_pct": 37.5,
            "moe.tick_expert_load_max_over_mean": 1.8,
            "moe.tick_experts_touched_pct": 95.0,
            "pool.live_latent_pct": 50.0,
            "served.prefill_tokens_per_tick": 512.0,
            "served.decode_rows_per_tick": 12.0,
            "served.tokens_per_s_slice_p50": 12000.0}
    for name, value in want.items():
        assert read(name) == pytest.approx(value), name
    # the parts and what no name covers add up to the tick
    named = sum(read(n) for n in NEW[1:10]
                if n != "served.unscoped_ms_per_tick")
    assert named + read("served.unscoped_ms_per_tick") == pytest.approx(60.0)
    # the attention's roofline: the yardstick's least time over 4 ms
    peak = yardstick.chip_peak("TPU v5 lite")
    least = ymd.attention_least_ms(
        real_config(), ((12 * 8001.0, 12 * 8001.0), (512 * 4500.0, 4756.0)),
        peak)
    assert read("mla.dense_attn_roofline_pct") == pytest.approx(
        100 * least / 4.0)
    for name in NEW[11:14]:
        assert 0 < read(name) < 100, name
    assert sorted(NEW) == sorted(
        f[:-3] for f in os.listdir(os.path.join(loader.HERE,
                                                "layer_metrics"))
        if f[:-3] in NEW)


def test_the_readers_find_nothing_in_a_program_without_the_model(
        monkeypatch):
    """The dots3 tick names ``blk/attn/mla`` and neither of this model's two
    attention scopes, and its family's facts hold no group hits: the
    readers of this cell's own mechanism return ``None`` there and raise
    nothing (the folded ones read that tick as dots3's: test_pb_fold.py);
    a GPT tick names no served mechanism at all and its facts hold no
    experts: every reader but the scheduler's and the pool's returns
    ``None``; so with no trace at all."""
    doc = _synthetic(["blk/qkv", "blk/attn/mla", "blk/ffn", "tick/head"])
    dots3 = loader.load_json(loader.root_file(
        "perfbench/configs/dots3-note-prev-serve.json"))
    run, pt = _run_with(doc, dots3, {
        "decode_rows_per_tick": 9.0, "prefill_rows_per_tick": 0.25,
        "prefill_chunk": 32, "live_kv_share": 0.5,
        "tick_expert_load_max_over_mean": 2.0,
        "tick_experts_touched_share": 0.9})
    monkeypatch.setattr(pt, "load", lambda: doc)
    for name in OWN:
        assert loader.load_module("layer_metrics", name).read(run) is None, \
            name
    doc = _synthetic(["blk/qkv", "blk/attn", "blk/ffn", "tick/head"])
    gpt = loader.load_json(loader.root_file(
        "perfbench/configs/gpt3-1.3b-serve.json"))
    run, pt = _run_with(doc, gpt, {
        "decode_rows_per_tick": 9.0, "prefill_rows_per_tick": 0.25,
        "prefill_chunk": 32, "live_kv_share": 0.5})
    monkeypatch.setattr(pt, "load", lambda: doc)
    for name in NEW[:17]:
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
    assert cell["cell"]["chips"] == 1 \
        and cell["cell"]["traffic"] == "docqa-8k-backlog"
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] \
                and m["moves"] == "serve_tokens_per_s"
        else:       # no other metric's list of cells names this cell
            assert CELL not in m.get("workloads", ())
        if m["name"] in OWN:
            assert m["workloads"] == [CELL]


# --- the check, controls included, through check() itself -------------------
@pytest.fixture(scope="module")
def served():
    """A toy engine that served five requests, and what ``check`` is
    handed: the context, the plan and a drive."""
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v2 import DeepseekV2
    from paddle_tpu.serving import ServingConfig, ServingEngine

    fam = loader.load_module("families", "deepseek_v2_serve")
    toy = toy_config()
    widths = {k: toy[k] for k in fam.PUBLISHED}
    c = dict(toy, family="deepseek_v2_serve")
    paddle.seed(5)
    net = DeepseekV2(fam.model_config(c, widths))
    net.eval()
    e = c["engine"]
    eng = ServingEngine(net, ServingConfig(
        num_slots=e["num_slots"], page_size=e["page_size"],
        pages_per_slot=e["pages_per_slot"], prefix_cache=False,
        prefill_chunks_per_tick=fam.PREFILL_CHUNKS_PER_TICK))
    rng = np.random.default_rng(9)
    requests = [{"prompt": rng.integers(0, 96, n, dtype=np.int32),
                 "max_new": m, "due_s": 0.0}
                for n, m in ((19, 20), (50, 24), (27, 12), (18, 30),
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
FLOAT32_LIMITS = (0.02, 0.01, 0.02)


def test_the_check_passes_what_the_engine_served(served):
    ctx, eng, plan, drive = served
    chk = loader.load_module("checks", "deepseek_v2_serve")
    picked = chk.sample(ctx, plan, drive, list(range(5)))
    assert picked[0] == 1 and len(picked) == len(set(picked)) == chk.SAMPLE
    watched = types.SimpleNamespace(
        eng=types.SimpleNamespace(tick_record=types.SimpleNamespace(
            has=lambda rid: rid in (0, 3))), rid_of=drive.rid_of)
    assert sorted(chk.sample(ctx, plan, watched, list(range(5)))) == [0, 3]
    assert chk.sample(ctx, plan, watched, [1, 2]) == []
    verdict = chk.check(ctx, eng.served_weights(), plan, drive,
                        list(range(5)), limits=FLOAT32_LIMITS)
    assert verdict["ok"], verdict["note"]
    assert verdict["note"].count("allowed") == 4
    assert not chk.check(ctx, eng.served_weights(), plan, drive, [])["ok"]


@pytest.mark.parametrize("control", [
    "fp8", "no_group_limit", "no_routed_scaling", "renormalised", "no_yarn",
    "no_mscale", "one_shared"])
def test_a_control_comes_out_not_correct(served, control):
    ctx, eng, plan, drive = served
    chk = loader.load_module("checks", "deepseek_v2_serve")
    verdict = chk.check(ctx, eng.served_weights(), plan, drive,
                        list(range(5)), control=control,
                        limits=FLOAT32_LIMITS)
    assert not verdict["ok"], verdict["note"]
    assert f"[{control}]" in verdict["note"]


# --- the cell, rehearsed on the CPU ------------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory, bench):
    dst = tmp_path_factory.mktemp("checkout_dsv2")
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
            m["workloads"].append("toy-dsv2-cell")
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def rehearse(copy, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(copy), loader.ROOT])}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_rehearsal.py"), "1",
         "--workload", "toy-dsv2-cell", "--seed", str(2 ** 31 + 11),
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
    assert "held experts used" in out


def test_a_traced_rehearsal_reports_what_the_cpu_can(copy):
    """The CPU's trace has no device plane: the device readers return
    nothing, the counters and the scheduler's readers report."""
    line, out = rehearse(copy, 1)
    assert line["correct"] is True, out[-2000:]
    got = set(line["metrics"])
    # the engine's own record of its ticks reads on the CPU too
    assert set(HOLDS) <= got
    assert {"moe.tick_group_hit_pct", "pool.live_latent_pct",
            "moe.tick_expert_load_max_over_mean",
            "moe.tick_experts_touched_pct",
            "served.prefill_tokens_per_tick",
            "served.decode_rows_per_tick",
            "served.tokens_per_s_slice_p50"} <= got
    assert 0 < line["metrics"]["moe.tick_group_hit_pct"]["value"] <= 100
    # two chunks a tick: more than one chunk's tokens in the mean tick
    assert line["metrics"]["served.prefill_tokens_per_tick"]["value"] > 8
