"""Laguna's cell: the configuration file against the catalog's row and its
family's ``check_widths``, the toy family through the contract's rules,
``yardstick_laguna``'s counts by hand, the cell's 27 readers on a synthetic
trace, the check and its controls through ``check()`` itself at a small size,
and a CPU rehearsal of the cell on a toy configuration in a temporary copy."""
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import loader, yardstick, yardstick_laguna as yl

from test_pb_contract import BACKLOG_HOLDS as HOLDS, config_file_is_sound, \
    family_is_only_a_model

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_laguna")
CELL = "serve-laguna-mixedlen-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARTS = ("lag.dense_ms_per_tick", "lag.head_sample_ms_per_tick",
         "lag.route_ms_per_tick", "lag.experts_ms_per_tick",
         "lag.shared_ms_per_tick", "lag.attn_full_ms_per_tick",
         "lag.attn_window_ms_per_tick", "lag.kv_scatter_ms_per_tick",
         "lag.unscoped_ms_per_tick")
SHARES = ("lag.tick_mfu_pct", "lag.tick_hbm_roofline_pct",
          "lag.experts_hbm_roofline_pct", "lag.attn_full_roofline_pct",
          "lag.attn_window_roofline_pct")
COUNTED = ("lag.live_kv_pct", "lag.window_pages_freed_per_tick",
           "lag.tokens_per_s_slice_p50", "lag.prefill_tokens_per_tick",
           "lag.decode_rows_per_tick", "lag.expert_load_max_over_mean",
           "lag.experts_touched_pct", "lag.host_ms_per_tick")
HELD = tuple("lag." + n.split(".", 1)[1] for n in HOLDS)
NEW = ("lag.tick_device_ms_p50",) + PARTS + SHARES + COUNTED + HELD
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "moe_intermediate_size",
          "shared_expert_intermediate_size", "num_experts_per_tok",
          "moe_routed_scaling_factor", "sliding_window",
          "max_position_embeddings")


def real_config():
    return loader.load_json(loader.root_file(
        "perfbench/configs/laguna-s-2.1-serve.json"))


def toy_config():
    return loader.load_json(os.path.join(TOY, "configs", "toy-laguna.json"))


@pytest.fixture(scope="module")
def bench():
    return loader.load_json(loader.root_file("BENCHMARK.json"))


# --- the configuration -----------------------------------------------------
def test_the_configuration_is_the_catalogs_row_cut_in_three_keys(bench):
    assert len(NEW) == len(set(NEW)) == 27
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1")
    c = real_config()
    entry = next(e for e in bench["configs"]
                 if e["name"] == "laguna-s-2.1-serve")
    assert entry["source"].startswith(row["source_url"] + "; cut: layers 0-5")
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c[key] != value and c["published"][key] == value
        else:
            assert c[key] == value, key     # nested groups whole
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) \
        == (6, 64, 25088) and c["published"]["chips"] == 32
    for said in ("gate", "router", "shared", "qk_norm", "rope", "window",
                 "initializer_range", "layouts", "lists"):
        assert said in c["assumed"]
    assert "v5litepod-32" in c["deployment"] and "eight pipeline stages of " \
        "six layers" in c["deployment"]
    config_file_is_sound(entry, c)
    e = c["engine"]
    assert e["num_slots"] == 38 and e["pages_per_slot"] * e["page_size"] \
        == 17664 and e["prefix_cache"] is False and e["decode"] == "greedy"


@pytest.mark.parametrize("key", WIDTHS)
def test_a_changed_width_is_refused_by_its_key(key):
    fam = loader.load_module("families", "laguna_serve")
    c = real_config()
    fam.check_widths(c)
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        fam.check_widths({**c, key: c[key] * 2})


@pytest.mark.parametrize("change,said", [
    ({"num_hidden_layers": 4}, "floor"),
    ({"num_experts": 4, "experts_held": [0, 4]}, "floor"),
    ({"vocab_size": 6272}, "floor"),
    ({"num_hidden_layers": 48}, "reduced lists"),
    ({"experts_held": [64, 64]}, "first of the four shares"),
    ({"moe_router_logit_softcapping": 30.0}, "moe_router_logit_softcapping"),
    ({"gating_types": ["per_head"] * 47 + ["per_token"]}, "gating_types"),
    ({"gating": "per-token"}, "gating"),
    ({"num_attention_heads_per_layer": [48] * 48},
     "num_attention_heads_per_layer"),
    ({"rope_parameters": {"full_attention": {}, "sliding_attention": {}}},
     "rope_parameters")])
def test_a_cut_past_the_floors_or_another_form_is_refused(change, said):
    fam = loader.load_module("families", "laguna_serve")
    with pytest.raises(ValueError, match=said):
        fam.check_widths({**real_config(), **change})


def test_the_family_builds_the_model_from_the_files_sizes():
    fam = loader.load_module("families", "laguna_serve")
    cfg = fam.model_config(real_config())
    assert cfg.num_hidden_layers == 6 and cfg.vocab_size == 25088
    assert cfg.num_experts == 256 and cfg.held == (0, 64)
    assert cfg.num_attention_heads_per_layer == (48, 72, 72, 72, 48, 72)
    assert cfg.mlp_layer_types[0] == "dense" and cfg.sliding_window == 512
    assert round(cfg.num_params() / 1e9, 2) == 3.68
    # the yardstick leaves out the selection bias the router's class carries
    assert cfg.num_params() - yl.total_params(real_config()) == 5 * 256
    assert fam.limits(real_config()) == {
        "vocab_size": 25088, "num_slots": 38, "capacity": 17664}
    assert fam.PREFILL_CHUNK == 256 and fam.FLOORS == {
        "num_hidden_layers": 5, "num_experts": 8, "vocab_size": 12544}


def test_the_traffic_is_issue_57s_and_fits_a_slot():
    traffic = loader.load_data("traffic", "mixedlen-4k-backlog")
    gen = loader.load_module("generators", traffic["generator"])
    fam = loader.load_module("families", "laguna_serve")
    assert traffic["requests"] == 600 and traffic["cycle"] == 4
    assert traffic["prompt"] == {"median": 4096, "sigma": 1.2, "lo": 512,
                                 "hi": 24576}
    assert traffic["output"] == {"median": 768, "sigma": 0.5, "lo": 256,
                                 "hi": 2048}
    assert traffic["warm_in_s"] == 20 and traffic["slices"] == 9 \
        and traffic["traced_s"] == 4
    others = [loader.load_json(os.path.join(loader.HERE, "traffic", f))
              for f in os.listdir(os.path.join(loader.HERE, "traffic"))
              if f != "mixedlen-4k-backlog.json"]
    assert traffic["order_seed"] not in {t.get("order_seed") for t in others}
    plan = gen.generate(traffic, 2 ** 31 + 5, 45.0, fam.limits(real_config()))
    sizes = {(len(r["prompt"]), r["max_new"]) for r in plan["requests"]}
    assert len(sizes) == 4
    assert {p for p, _ in sizes} == {1030, 2794, 6004, 16288}
    assert {o for _, o in sizes} == {432, 655, 901, 1365}
    # nothing is truncated, whatever the pairing: 17,653 of 17,664
    assert 16288 + 1365 <= 17664
    assert plan["mode"] == "closed" and len(plan["requests"]) == 600
    assert all(r["prompt"].max() < 25088 for r in plan["requests"][:8])


def test_the_benchmarks_reference_is_the_programs_copy():
    def body(path):
        with open(loader.root_file(path), encoding="utf-8") as f:
            text = f.read()
        return text[text.index("With ``N(.)``"):]

    mine = body("perfbench/references/laguna.py")
    assert mine == body("paddle_tpu/models/laguna_reference.py")
    assert "import paddle_tpu" not in mine and "from paddle_tpu" not in mine
    src = open(loader.root_file("perfbench/yardstick_laguna.py")).read()
    assert "paddle_tpu" not in src.split('"""')[2]


# --- the toy family, through the contract's rules ---------------------------
@pytest.fixture
def with_toy(tmp_path):
    dst, real = str(tmp_path / "perfbench"), loader.HERE
    shutil.copytree(real, dst, ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("families", "checks"):
        shutil.copy(os.path.join(TOY, kind, "toy_laguna.py"),
                    os.path.join(dst, kind, "toy_laguna.py"))
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
                                        "toy_laguna.py"))
    family_is_only_a_model(os.path.join(with_toy, "families",
                                        "laguna_serve.py"))
    cfg = toy_config()
    config_file_is_sound({"name": "toy-laguna", "reduced": cfg["reduced"]},
                         cfg)
    real = loader.load_module("families", "laguna_serve")
    with pytest.raises(ValueError, match="hidden_size"):
        real.check_widths(cfg)          # the shipped family holds to 3,072


def test_the_loader_finds_every_piece_of_the_cell(bench):
    cell = loader.load_cell(CELL)
    c = cell["config"]
    assert c["name"] == "laguna-s-2.1-serve"
    for kind, name in (("families", c["family"]), ("checks", c["family"]),
                       ("references", c["reference"])):
        assert loader.load_module(kind, name)
    assert loader.load_module("generators", cell["traffic"]["generator"])
    assert callable(loader.load_module("families", c["family"]).run)
    for name in NEW:
        assert callable(loader.load_module("layer_metrics", name).read), name
    # the helper is in the served form but for the name of ``needs``: two
    # accepted tests hold ``_served``'s list of helpers closed
    helper = loader.load_module("layer_metrics", "_laguna_trace")
    assert hasattr(helper, "needs") and not hasattr(helper, "tick_needs") \
        and hasattr(helper, "least_ms") \
        and helper.MECHANISM == ("blk/attn/window",)
    served = loader.load_module("layer_metrics", "_served")
    assert "_laguna_trace" not in served.helpers()
    assert len(bench["workloads"]) >= 13 and len(bench["configs"]) >= 12
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_cells_lists_name_the_new_metrics_of_this_cell(bench):
    cell = loader.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    assert cell["cell"]["chips"] == 1 \
        and cell["cell"]["traffic"] == "mixedlen-4k-backlog"
    mine = [m for m in bench["per_layer"] if m["name"].startswith("lag.")]
    assert sorted(m["name"] for m in mine) == sorted(NEW)
    assert len(bench["per_layer"]) <= 128
    for m in mine:
        assert m["workloads"] == [CELL] \
            and m["moves"] == "serve_tokens_per_s", m["name"]
    assert any("mfu" in m["name"] for m in mine)
    for m in bench["per_layer"]:
        if not m["name"].startswith("lag."):
            assert CELL not in m.get("workloads", ())
    assert {m["layer"] for m in mine} == {
        "serving tick (device)", "serving scheduler (host)",
        "paged attention / page pool", "expert routing and grouped matmul"}


# --- the yardstick, against hand arithmetic --------------------------------
def test_the_yardstick_counts_what_issue_57_reckons():
    c = real_config()
    m = lambda n: round(n / 1e6, 1)                          # noqa: E731
    assert m(yl.attention_params(c, 48)) == 44.2
    assert m(yl.attention_params(c, 72)) == 63.1
    assert yl.kinds(c).count("full_attention") == 2
    assert yl.heads_of(c, yl.FULL) == 48 and yl.heads_of(c, yl.SLIDING) == 72
    # five sparse layers x 64 experts x 9.437 M: 3.02 B, 6.04 GB
    assert round(yl.held_params(c) * 2 / 1e9, 2) == 6.04
    assert round(yl.total_params(c) * 2 / 1e9, 2) == 7.36
    # K and V: 8 heads x 128 x 2 x 2 B = 4,096 B a key a layer
    assert yl.attention_bytes(c, yl.FULL, 1.0) == 2 * 4096
    assert yl.attention_bytes(c, yl.SLIDING, 1.0) == 4 * 4096
    assert yl.attention_flops(c, yl.FULL, 1.0) == 2 * 4 * 48 * 128
    assert yl.attention_flops(c, yl.SLIDING, 1.0) == 4 * 4 * 72 * 128
    peak = yardstick.chip_peak("TPU v5 lite")
    # ISSUE 57's tick: 32 decode rows 7 k deep read 1.84 GB in the two full
    # layers, 2.2 ms at the HBM roofline
    moved = yl.attention_bytes(c, yl.FULL, 32 * 7000.0)
    assert 1.8e9 < moved < 1.9e9
    assert 2.2 < yl.least_ms(0.0, moved, peak) < 2.3
    # every held expert touched: 6.04 GB, 7.4 ms
    assert 7.3 < yl.experts_bytes(c, 1.0) / peak.hbm_bytes_per_s * 1e3 < 7.5


def test_the_yardstick_on_a_hand_worked_tick_at_the_toys_widths():
    c = toy_config()
    # the toy: five layers (full + dense, sliding x 3, full), hidden 32,
    # 4 and 6 heads over 2 K/V heads of 16, 4 of 8 experts of 16 held
    attn = lambda nh: 32 * (2 * nh * 16 + 2 * 2 * 16 + nh)   # noqa: E731
    assert yl.attention_params(c, 4) == attn(4)
    dense = attn(4) * 2 + attn(6) * 3 + 5 * 2 * 32 + 3 * 32 * 48 \
        + 4 * (32 * 8 + 3 * 32 * 16)
    assert yl.dense_params(c) == dense
    assert yl.held_params(c) == 4 * 4 * 3 * 32 * 16
    assert yl.layers_of(c, yl.FULL) == 2 and yl.layers_of(c, yl.SLIDING) == 3
    s = {"decode": 3.0, "chunk": 10.0, "sampled": 3.0, "touched": 0.75,
         "expert_rows": 9.0, "decode_keys": 60.0, "chunk_keys": 18.0,
         "chunk_pairs": 125.0, "window_decode_keys": 18.0,
         "window_chunk_keys": 15.0, "window_chunk_pairs": 55.0}
    written = 13 * 5 * 2 * 2 * 16 * 2
    assert yl.tick_bytes(c, s) == dense * 2 + 0.75 * yl.held_params(c) * 2 \
        + 32 * 96 * 2 + 13 * 32 * 2 + written \
        + 2 * 78 * 2 * 2 * 16 * 2 + 3 * 33 * 2 * 2 * 16 * 2
    assert yl.tick_flops(c, s) == pytest.approx(
        2.0 * dense * 13 + 2.0 * 4 * 9 * 3 * 32 * 16 + 2.0 * 3 * 32 * 96
        + 2 * 185 * 4 * 4 * 16 + 3 * 73 * 4 * 6 * 16)


# --- the readers, on a synthetic trace ------------------------------------
def _op(name, scope, t0, dur):
    return {"name": name, "scope": scope, "start_ns": t0, "dur_ns": dur}


def _synthetic(scopes):
    """Two whole 30 ms runs of ``jit_tick`` on one device plane, each with
    one operation a scope, 2 ms long, and 3 ms under no scope."""
    ops, runs = [], []
    for r in range(2):
        t0 = r * 40_000_000
        runs.append({"name": "jit_tick(1)", "start_ns": t0,
                     "dur_ns": 30_000_000})
        for i, scope in enumerate(scopes):
            ops.append(_op(f"fusion.{i}", f"jit(tick)/{scope}/dot",
                           t0 + i * 2_000_000, 2_000_000))
        ops.append(_op("copy.1", "jit(tick)", t0 + 26_000_000, 3_000_000))
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": runs},
        {"name": "XLA Ops", "events": ops}]}]}


def _run_with(doc, config, facts):
    pt = loader.load_module("layer_metrics", "_program_trace")
    ctx = types.SimpleNamespace(
        trace_doc=doc, config=config,
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    return {"ctx": ctx, "facts": facts, "notes": []}, pt


FACTS = {"decode_rows_per_tick": 31.0, "prefill_rows_per_tick": 0.9,
         "prefill_chunk": 256, "live_kv_share": 0.41,
         "serve_tokens_per_s_slice_p50": 12000.0,
         "window_pages_freed_per_tick": 16.5,
         "tick_decode_rows": 30.0, "tick_chunk_tokens": 230.0,
         "tick_decode_keys": 30 * 6000.0, "tick_chunk_keys": 5000.0,
         "tick_chunk_pairs": 230 * 5000.0,
         "tick_window_decode_keys": 30 * 512.0,
         "tick_window_chunk_keys": 700.0,
         "tick_window_chunk_pairs": 230 * 500.0, "tick_expert_rows": 650.0,
         "tick_expert_load_max_over_mean": 2.1,
         "tick_experts_touched_share": 0.98,
         "tick_held_rows_unaccounted": 0.0}
SCOPES = ["blk/qkv", "blk/kv_scatter", "blk/attn/full/grouped_paged_attn",
          "blk/attn/window/grouped_window_attn", "blk/attn_out", "blk/ffn",
          "blk/ffn/moe/route", "blk/ffn/moe/dispatch", "blk/ffn/moe/experts",
          "blk/ffn/moe/combine", "blk/ffn/moe/shared", "tick/embed",
          "tick/head"]


def test_the_readers_split_a_tick_by_the_programs_names(monkeypatch):
    doc = _synthetic(SCOPES)
    run, pt = _run_with(doc, real_config(), dict(FACTS))
    monkeypatch.setattr(pt, "load", lambda: doc)
    read = lambda name: loader.load_module("layer_metrics", name).read(run)
    want = {"lag.tick_device_ms_p50": 30.0, "lag.dense_ms_per_tick": 6.0,
            "lag.head_sample_ms_per_tick": 4.0,
            "lag.attn_full_ms_per_tick": 2.0,
            "lag.attn_window_ms_per_tick": 2.0,
            "lag.kv_scatter_ms_per_tick": 2.0, "lag.route_ms_per_tick": 2.0,
            "lag.experts_ms_per_tick": 6.0, "lag.shared_ms_per_tick": 2.0,
            "lag.live_kv_pct": 41.0, "lag.window_pages_freed_per_tick": 16.5,
            "lag.tokens_per_s_slice_p50": 12000.0,
            "lag.prefill_tokens_per_tick": 0.9 * 256,
            "lag.decode_rows_per_tick": 31.0,
            "lag.expert_load_max_over_mean": 2.1,
            "lag.experts_touched_pct": 98.0}
    for name, value in want.items():
        assert read(name) == pytest.approx(value), name
    # the parts and what no name covers add up to the tick
    assert sum(read(n) for n in PARTS) == pytest.approx(30.0)
    peak = yardstick.chip_peak("TPU v5 lite")
    c = real_config()
    s = loader.load_module("layer_metrics", "_laguna_trace").tick_shape(run)
    assert s["decode"] == 30.0 and s["sampled"] == 31.0
    assert read("lag.attn_full_roofline_pct") == pytest.approx(
        100 * yl.least_ms(
            yl.attention_flops(c, yl.FULL, 30 * 6000.0 + 230 * 5000.0),
            yl.attention_bytes(c, yl.FULL, 30 * 6000.0 + 5000.0), peak) / 2.0)
    assert read("lag.attn_window_roofline_pct") == pytest.approx(
        100 * yl.least_ms(
            yl.attention_flops(c, yl.SLIDING, 30 * 512.0 + 230 * 500.0),
            yl.attention_bytes(c, yl.SLIDING, 30 * 512.0 + 700.0), peak)
        / 2.0)
    assert read("lag.experts_hbm_roofline_pct") == pytest.approx(
        100 * yl.experts_bytes(c, 0.98) / peak.hbm_bytes_per_s * 1e3 / 6.0)
    assert read("lag.tick_hbm_roofline_pct") == pytest.approx(
        100 * yl.tick_bytes(c, s) / peak.hbm_bytes_per_s * 1e3 / 30.0)
    assert read("lag.tick_mfu_pct") == pytest.approx(
        100 * yl.tick_flops(c, s) / 30e-3 / peak.bf16_flops)
    for name in SHARES:
        assert 0 < read(name), name
    # the served families' helpers do not read this tick: none of the
    # accepted readers answers for it, and the trace is cut once
    for other in ("_dots3_trace", "_dsv2_trace", "_olmoh_trace",
                  "_ling3_trace", "_falcon_h1_trace"):
        assert loader.load_module("layer_metrics", other).parts_ms(run) \
            is None, other
    assert loader.load_module("layer_metrics", "_served").trace_of(run) \
        is None
    assert pt.cuts_of(doc) == ["_laguna_trace"]


def test_the_readers_find_nothing_in_a_program_without_the_model(
        monkeypatch):
    """A served GPT's tick names ``blk/attn`` and ``blk/ffn`` and no
    ``blk/attn/window``, and its family's facts hold no windowed keys: every
    reader of the trace returns ``None`` and raises nothing; so with no
    trace at all. Falcon-H1's tick (``blk/attn/full`` under
    ``blk/ssd/step``) is not this helper's either."""
    gpt = loader.load_json(loader.root_file(
        "perfbench/configs/gpt3-1.3b-serve.json"))
    falcon = _synthetic(["blk/ssd/step", "blk/qkv", "blk/attn/full",
                         "blk/ffn", "tick/head"])
    run, pt = _run_with(falcon, gpt, {})
    monkeypatch.setattr(pt, "load", lambda: falcon)
    assert loader.load_module("layer_metrics", "_laguna_trace").parts_ms(
        run) is None
    doc = _synthetic(["blk/qkv", "blk/attn", "blk/ffn", "tick/head"])
    run, pt = _run_with(doc, gpt, {
        "decode_rows_per_tick": 9.0, "prefill_rows_per_tick": 0.25,
        "prefill_chunk": 32, "live_kv_share": 0.5})
    monkeypatch.setattr(pt, "load", lambda: doc)
    helper = loader.load_module("layer_metrics", "_laguna_trace")
    assert helper.parts_ms(run) is None and helper.needs(run) is None \
        and helper.least_ms(run, "attn") is None
    for name in ("lag.attn_window_ms_per_tick",
                 "lag.attn_window_roofline_pct", "lag.experts_ms_per_tick",
                 "lag.experts_hbm_roofline_pct",
                 "lag.window_pages_freed_per_tick",
                 "lag.expert_load_max_over_mean"):
        assert loader.load_module("layer_metrics", name).read(run) is None, \
            name
    run["ctx"].trace_doc = None
    for name in ("lag.tick_mfu_pct", "lag.tick_device_ms_p50",
                 "lag.attn_full_roofline_pct"):
        assert loader.load_module("layer_metrics", name).read(run) is None


# --- the check, controls included, through check() itself -------------------
@pytest.fixture(scope="module")
def served():
    """A toy engine that served four requests and still decodes two, and
    what ``check`` is handed: the context, the plan and a drive."""
    import paddle_tpu as paddle
    from paddle_tpu.models.laguna import Laguna
    from paddle_tpu.profiler import registry
    from paddle_tpu.serving import ServingConfig, ServingEngine

    toy_fam = {}
    exec(open(os.path.join(TOY, "families", "toy_laguna.py")).read(), toy_fam)
    fam = loader.load_module("families", "laguna_serve")
    toy = dict(toy_config(), family="laguna_serve")
    paddle.seed(5)
    net = Laguna(fam.model_config(toy, **toy_fam["TABLES"]))
    net.eval()
    e = toy["engine"]
    eng = ServingEngine(net, ServingConfig(
        num_slots=e["num_slots"], page_size=e["page_size"],
        pages_per_slot=e["pages_per_slot"], prefill_chunk=8,
        prefix_cache=False))
    rng = np.random.default_rng(9)
    requests = [{"prompt": rng.integers(0, 96, n, dtype=np.int32),
                 "max_new": m, "due_s": 0.0}
                for n, m in ((19, 20), (41, 24), (27, 12), (18, 16),
                             (33, 50), (22, 50))]
    rids = [eng.submit(r["prompt"], r["max_new"]) for r in requests]
    finished = []
    while len(finished) < 4:
        eng.step()
        eng.drain(0)
        finished = [i for i, r in enumerate(requests)
                    if len(eng.tokens_so_far(rids[i])) >= r["max_new"]]
    drive = types.SimpleNamespace(
        eng=eng, rid_of=dict(enumerate(rids)), reg=registry(),
        output=lambda i: np.asarray(eng.tokens_so_far(rids[i]), np.int32))
    ctx = types.SimpleNamespace(config=toy, seed=2 ** 31 + 3)
    return ctx, eng, {"requests": requests}, drive, finished


#: the fixture serves float32, which the reference repeats but for the
#: order of its sums: the shipped limits are bf16's at the published widths
FLOAT32_LIMITS = (0.02, 0.01, 0.02, 0.001, 0.001)


def test_the_check_passes_what_the_engine_served(served):
    ctx, eng, plan, drive, finished = served
    chk = loader.load_module("checks", "laguna_serve")
    live = chk.still_decoding(ctx, plan, drive, finished)
    assert 1 <= len(live) <= 2
    assert all(i not in finished for i, _, _ in live)
    # the longest finished request is always among the compared: in the cell
    # that is the only traffic past YaRN's original positions
    assert chk.sample(ctx, plan, drive, finished)[0] == 1
    verdict = chk.check(ctx, eng.served_weights(), plan, drive, finished,
                        limits=FLOAT32_LIMITS)
    assert verdict["ok"], verdict["note"]
    assert verdict["note"].count("allowed") == 7
    assert "requests of 64/" in verdict["note"]
    assert "slots still decoding" in verdict["note"]
    assert not chk.check(ctx, eng.served_weights(), plan, drive, [])["ok"]
    with pytest.raises(ValueError, match="unknown control"):
        chk.check(ctx, eng.served_weights(), plan, drive, finished,
                  control="rope")


@pytest.mark.parametrize("control", [
    "fp8", "no_gate", "no_window", "no_yarn", "full_rotary",
    "no_attention_factor", "softmax_router", "no_routed_scaling",
    "no_shared", "fp8_kv"])
def test_a_control_comes_out_not_correct(served, control):
    ctx, eng, plan, drive, finished = served
    chk = loader.load_module("checks", "laguna_serve")
    assert control in chk.controls(ctx.config)
    verdict = chk.check(ctx, eng.served_weights(), plan, drive, finished,
                        control=control, limits=FLOAT32_LIMITS)
    assert not verdict["ok"], verdict["note"]
    assert f"[{control}]" in verdict["note"]


# --- the cell, rehearsed on the CPU ------------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory, bench):
    dst = tmp_path_factory.mktemp("checkout_laguna")
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
            m["workloads"].append("toy-laguna-cell")
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def test_a_traced_rehearsal_reports_what_the_cpu_can(copy):
    """The cell's control flow end to end on the CPU (a rehearsal, no
    number): the CPU's trace has no device plane, so the device readers
    return nothing; the counters and the scheduler's readers report."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(copy), loader.ROOT])}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_rehearsal.py"), "1",
         "--workload", "toy-laguna-cell", "--seed", str(2 ** 31 + 11),
         "--seconds", "1.5", "--trace", "1"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    line, out = json.loads(lines[-1]), p.stdout
    assert line["correct"] is True, out[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    got = set(line["metrics"])
    assert set(HELD) <= got     # the engine's own record reads here too
    assert {"lag.live_kv_pct", "lag.window_pages_freed_per_tick",
            "lag.tokens_per_s_slice_p50", "lag.prefill_tokens_per_tick",
            "lag.decode_rows_per_tick", "lag.expert_load_max_over_mean",
            "lag.experts_touched_pct"} <= got
    assert line["metrics"]["lag.window_pages_freed_per_tick"]["value"] > 0
    assert "slots still decoding" in out
