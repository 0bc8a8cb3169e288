"""Falcon-H1's cell: the configuration file against the catalog's row and its
family's ``check_widths``, the toy family through the contract's rules,
``yardstick_ssd``'s counts by hand, the cell's 23 readers on a synthetic trace,
the check and its controls through ``check()`` itself at a small size, and a
CPU rehearsal of the cell on a toy configuration in a temporary copy."""
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import loader, yardstick, yardstick_ssd as ys

from test_pb_contract import BACKLOG_HOLDS as HOLDS, config_file_is_sound, \
    family_is_only_a_model

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_falcon_h1")
CELL = "serve-falcon-h1-gen-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# The cell's 23 quantities under the names they carry since PR 56: every one
# is an entry another cell reports too (``served.*``, ``state.*``,
# ``attn.full_*``, ``pool.*``; the cell's own ``fh1.*`` and ``ssd.*`` copies
# went), none lists this cell alone.
PARTS = ("served.dense_ms_per_tick", "served.head_sample_ms_per_tick",
         "state.step_ms_per_tick", "state.chunk_ms_per_tick",
         "state.prep_ms_per_tick", "attn.full_ms_per_tick",
         "served.unscoped_ms_per_tick")
SHARES = ("served.tick_mfu_pct", "served.tick_hbm_roofline_pct",
          "state.step_hbm_roofline_pct", "state.chunk_roofline_pct",
          "attn.full_roofline_pct")
COUNTED = ("pool.live_state_slots_pct", "pool.live_kv_pct.backlog",
           "served.tokens_per_s_slice_p50", "served.prefill_tokens_per_tick",
           "served.decode_rows_per_tick", "served.host_ms_per_tick")
NEW = ("served.tick_device_ms_p50",) + PARTS + SHARES + COUNTED + HOLDS
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "mamba_d_ssm", "mamba_n_heads",
          "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
          "mamba_chunk_size", "max_position_embeddings", "rope_theta",
          "embedding_multiplier", "lm_head_multiplier", "ssm_in_multiplier",
          "ssm_out_multiplier", "attention_out_multiplier", "key_multiplier")


def real_config():
    return loader.load_json(loader.root_file(
        "perfbench/configs/falcon-h1-34b-serve.json"))


def toy_config():
    return loader.load_json(os.path.join(TOY, "configs",
                                         "toy-falcon-h1.json"))


@pytest.fixture(scope="module")
def bench():
    return loader.load_json(loader.root_file("BENCHMARK.json"))


# --- the configuration -----------------------------------------------------
def test_the_configuration_is_the_catalogs_row_cut_in_two_keys(bench):
    assert len(NEW) == 23
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    c = real_config()
    entry = next(e for e in bench["configs"]
                 if e["name"] == "falcon-h1-34b-serve")
    assert entry["source"].startswith(row["source_url"] + "; cut: 9 of 72")
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers",
                                                "vocab_size"]
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c[key] != value and c["published"][key] == value
        else:
            assert c[key] == value, key     # nested groups whole
    assert c["num_hidden_layers"] == 9 and c["vocab_size"] == 32640 \
        and c["published"]["chips"] == 8
    for said in ("ssm_multipliers", "dt", "gated_norm", "groups", "gates",
                 "conv", "initializer_range", "rope", "state", "layouts"):
        assert said in c["assumed"]
    assert "v5e-8" in c["deployment"] and "eight pipeline stages of nine " \
        "layers" in c["deployment"]
    config_file_is_sound(entry, c)
    e = c["engine"]
    assert e["num_slots"] == 80 and e["pages_per_slot"] * e["page_size"] \
        == 1408 and e["prefix_cache"] is False and e["decode"] == "greedy"


@pytest.mark.parametrize("key", WIDTHS)
def test_a_changed_width_is_refused_by_its_key(key):
    fam = loader.load_module("families", "falcon_h1_serve")
    c = real_config()
    fam.check_widths(c)
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        fam.check_widths({**c, key: c[key] * 2})


@pytest.mark.parametrize("change,said", [
    ({"num_hidden_layers": 3}, "floor"),
    ({"vocab_size": 16320}, "floor"),
    ({"num_hidden_layers": 72}, "reduced lists"),
    ({"published": {"num_hidden_layers": 64, "vocab_size": 261120}},
     "published.num_hidden_layers"),
    ({"ssm_multipliers": [0.25, 0.3535533905932738, 0.1767766952966369, 0.5,
                          0.3535533905932738]}, "ssm_multipliers"),
    ({"mlp_multipliers": [0.1767766952966369, 1.0]}, "mlp_multipliers")])
def test_a_cut_past_the_floors_or_a_moved_multiplier_is_refused(change, said):
    fam = loader.load_module("families", "falcon_h1_serve")
    with pytest.raises(ValueError, match=said):
        fam.check_widths({**real_config(), **change})


def test_the_family_builds_the_model_from_the_files_sizes():
    fam = loader.load_module("families", "falcon_h1_serve")
    cfg = fam.model_config(real_config())
    assert cfg.num_hidden_layers == 9 and cfg.vocab_size == 32640
    assert cfg.conv_width == 5120 and cfg.proj_width == 9248
    assert cfg.ssm_multipliers[3] == 0.5 and cfg.rope_theta == 1e11
    assert round(cfg.num_params() / 1e9, 3) == 4.205
    assert cfg.num_params() == ys.total_params(real_config())
    assert fam.limits(real_config()) == {
        "vocab_size": 32640, "num_slots": 80, "capacity": 1408}
    assert fam.PREFILL_CHUNK == 256
    with pytest.raises(ValueError, match="untied head"):
        fam.model_config({**real_config(), "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="attention in every layer"):
        fam.model_config({**real_config(), "attn_layer_indices": [0, 4]})


def test_the_traffic_is_issue_54s_and_fits_a_slot():
    traffic = loader.load_data("traffic", "gen-640-backlog")
    gen = loader.load_module("generators", traffic["generator"])
    fam = loader.load_module("families", "falcon_h1_serve")
    assert traffic["requests"] == 800 and traffic["cycle"] == 8
    assert traffic["prompt"] == {"median": 320, "sigma": 0.4, "lo": 128,
                                 "hi": 512}
    assert traffic["output"] == {"median": 640, "sigma": 0.3, "lo": 384,
                                 "hi": 896}
    assert traffic["warm_in_s"] == 20 and traffic["slices"] == 9 \
        and traffic["traced_s"] == 4
    others = [loader.load_json(os.path.join(loader.HERE, "traffic", f))
              for f in os.listdir(os.path.join(loader.HERE, "traffic"))
              if f != "gen-640-backlog.json"]
    assert traffic["order_seed"] not in {t.get("order_seed") for t in others}
    plan = gen.generate(traffic, 2 ** 31 + 5, 45.0, fam.limits(real_config()))
    sizes = {(len(r["prompt"]), r["max_new"]) for r in plan["requests"]}
    assert len(sizes) == 8
    assert {p for p, _ in sizes} == {173, 224, 263, 300, 341, 389, 456, 512}
    assert {o for _, o in sizes} == {404, 490, 553, 610, 671, 741, 835, 896}
    # nothing is truncated: the longest pair fits a slot
    assert max(p + o for p, o in sizes) <= 1408
    assert plan["mode"] == "closed" and len(plan["requests"]) == 800
    assert all(r["prompt"].max() < 32640 for r in plan["requests"][:8])


def test_the_benchmarks_reference_is_the_programs_copy():
    def body(path):
        with open(loader.root_file(path), encoding="utf-8") as f:
            text = f.read()
        return text[text.index("With ``N(.)``"):]

    mine = body("perfbench/references/falcon_h1.py")
    assert mine == body("paddle_tpu/models/falcon_h1_reference.py")
    assert "import paddle_tpu" not in mine and "from paddle_tpu" not in mine
    assert "lax.scan" in mine and "ops" not in [
        w.strip(".,") for w in mine.split('"""')[1].split()]
    src = open(loader.root_file("perfbench/yardstick_ssd.py")).read()
    assert "paddle_tpu" not in src.split('"""')[2]


# --- the toy family, through the contract's rules ---------------------------
@pytest.fixture
def with_toy(tmp_path):
    dst, real = str(tmp_path / "perfbench"), loader.HERE
    shutil.copytree(real, dst, ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("families", "checks"):
        shutil.copy(os.path.join(TOY, kind, "toy_falcon_h1.py"),
                    os.path.join(dst, kind, "toy_falcon_h1.py"))
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
                                        "toy_falcon_h1.py"))
    family_is_only_a_model(os.path.join(with_toy, "families",
                                        "falcon_h1_serve.py"))
    cfg = toy_config()
    config_file_is_sound({"name": "toy-falcon-h1",
                          "reduced": cfg["reduced"]}, cfg)
    real = loader.load_module("families", "falcon_h1_serve")
    with pytest.raises(ValueError, match="hidden_size"):
        real.check_widths(cfg)          # the shipped family holds to 5,120


@pytest.mark.parametrize("key", ["hidden_size", "mamba_d_state",
                                 "num_key_value_heads", "key_multiplier"])
def test_the_toy_family_refuses_each_changed_width_by_name(with_toy, key):
    cfg = toy_config()
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        config_file_is_sound(
            {"name": "toy-falcon-h1", "reduced": cfg["reduced"]},
            {**cfg, key: cfg[key] * 2})


def test_the_loader_finds_every_piece_of_the_cell(bench):
    cell = loader.load_cell(CELL)
    c = cell["config"]
    assert c["name"] == "falcon-h1-34b-serve"
    for kind, name in (("families", c["family"]), ("checks", c["family"]),
                       ("references", c["reference"])):
        assert loader.load_module(kind, name)
    assert loader.load_module("generators", cell["traffic"]["generator"])
    assert callable(loader.load_module("families", c["family"]).run)
    for name in NEW:
        assert callable(loader.load_module("layer_metrics", name).read), name
    # the helper is in the served form since PR 56: ``_served`` lists it
    helper = loader.load_module("layer_metrics", "_falcon_h1_trace")
    assert hasattr(helper, "tick_needs") and hasattr(helper, "least_ms") \
        and not hasattr(helper, "needs")
    served = loader.load_module("layer_metrics", "_served")
    assert "_falcon_h1_trace" in served.helpers()
    assert len(bench["workloads"]) >= 12 and len(bench["configs"]) >= 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


# --- the yardstick, against hand arithmetic --------------------------------
def test_the_yardstick_counts_what_issue_54_reckons():
    c = real_config()
    m = lambda n: round(n / 1e6, 2)                          # noqa: E731
    # W_in 5120 x 9248 = 47.35 M, W_out 4096 x 5120 = 20.97 M, the rest 0.03
    assert m(ys.ssd_mixer_params(c)) == 68.35
    assert m(ys.attention_params(c)) == 31.46           # 13.11 + 5.24 + 13.11
    assert m(ys.ffn_params(c)) == 330.31
    assert round(ys.layer_params(c) / 9e6, 1) == 430.1
    assert round(ys.total_params(c) * 2 / 1e9, 2) == 8.41
    assert ys.state_entries(c) * 4 == 4194304          # 4.19 MB a state
    assert ys.conv_width(c) == 5120
    peak = yardstick.chip_peak("TPU v5 lite")
    # 80 live rows: 9 x 80 x 2 x 4.19 MB = 6.04 GB of states, 7.4 ms
    moved = ys.step_bytes(c, 80.0)
    assert 6.04e9 < moved < 6.08e9
    assert 7.3 < ys.least_ms(ys.step_flops(c, 80.0), moved, peak) < 7.5
    # K and V: 9 x 2 x 4 x 128 x 2 B = 18,432 B a key, no padded head
    assert ys.attention_bytes(c, 1.0) == 18432
    # 80 rows at 660 keys: 0.97 GB
    assert 0.96e9 < ys.attention_bytes(c, 80 * 660.0) < 0.98e9
    # one read of the layers and the head: 8.07 GB, 9.9 ms
    shape = {"live": 0.0, "chunk": 0.0, "chunk_rows": 0.0, "sampled": 0.0,
             "decode_keys": 0.0, "chunk_keys": 0.0, "chunk_pairs": 0.0}
    assert 8.06e9 < ys.tick_bytes(c, shape) < 8.09e9
    # ISSUE 54's decode tick: 15.1 GB, 18.4 ms at the HBM roofline
    shape.update(live=80.0, sampled=80.0, decode_keys=80 * 660.0)
    assert 15.0e9 < ys.tick_bytes(c, shape) < 15.2e9
    assert 18.3 < ys.tick_bytes(c, shape) / peak.hbm_bytes_per_s * 1e3 < 18.6


def test_the_yardstick_on_a_hand_worked_tick_at_the_toys_widths():
    c = toy_config()
    # the toy: 3 layers, 4 heads of 8 x 16 in 2 groups, 4 query heads over 2
    # key/value heads of 16, hidden 64, SwiGLU of 96, vocabulary 96
    proj = 32 + (32 + 2 * 2 * 16) + 4                   # [z | x B C | dt]
    ssd = 64 * proj + 32 * 64 + 5 * 96 + 3 * 4 + 32
    attn = 64 * (64 + 2 * 32) + 64 * 64
    ffn = 3 * 64 * 96 + 2 * 64
    assert ys.conv_width(c) == 96
    assert ys.ssd_mixer_params(c) == ssd and ys.attention_params(c) == attn
    assert ys.layer_params(c) == 3 * (ssd + attn + ffn)
    assert ys.total_params(c) == 3 * (ssd + attn + ffn) + 2 * 96 * 64 + 64
    entries = 4 * 8 * 16
    assert ys.state_entries(c) == entries
    # a row's operands: x 32 + B, C 2 x 32 in bf16, dt 4 and y 32 in float32
    row = (32 + 64) * 2 + 4 * 4 + 32 * 4
    assert ys.step_bytes(c, 3.0) == 3 * 3 * (2 * entries * 4 + row)
    assert ys.step_flops(c, 3.0) == 3 * 3 * 5 * entries
    assert ys.chunk_bytes(c, 10.0, 1.0) == 3 * (10 * row + 2 * entries * 4)
    # a chunk token: 2 groups' scores over half a block of 128 (128 x 16
    # each), a head's use of them (128 x 8) and its two state products
    assert ys.chunk_flops(c, 1.0) == 3 * (2 * 128 * 16
                                         + 4 * (128 * 8 + 4 * 16 * 8))
    assert ys.attention_bytes(c, 7.0) == 3 * 7 * 2 * 2 * 16 * 2
    assert ys.attention_flops(c, 7.0) == 3 * 7 * 4 * 4 * 16
    shape = {"live": 3.0, "chunk": 10.0, "chunk_rows": 1.0, "sampled": 3.0,
             "decode_keys": 60.0, "chunk_keys": 18.0, "chunk_pairs": 125.0}
    written = 13 * 3 * (2 * 2 * 16 + 96) * 2
    assert ys.tick_bytes(c, shape) == (
        3 * (ssd + attn + ffn) + 96 * 64 + 64) * 2 + 13 * 64 * 2 \
        + ys.step_bytes(c, 3.0) + ys.chunk_bytes(c, 10.0, 1.0) \
        + ys.attention_bytes(c, 78.0) + written
    assert ys.tick_flops(c, shape) == pytest.approx(
        2.0 * 3 * (ssd + attn + ffn) * 13 + 2.0 * 96 * 64 * 3
        + ys.step_flops(c, 3.0) + ys.chunk_flops(c, 10.0)
        + ys.attention_flops(c, 185.0))


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


FACTS = {"decode_rows_per_tick": 80.0, "prefill_rows_per_tick": 0.2,
         "prefill_chunk": 256, "live_kv_share": 0.47,
         "serve_tokens_per_s_slice_p50": 4000.0,
         "tick_live_state_rows": 79.0, "tick_chunk_tokens": 50.0,
         "tick_decode_keys": 79 * 660.0, "tick_chunk_keys": 120.0,
         "tick_chunk_pairs": 50 * 300.0, "live_state_share": 0.975,
         "state_bytes": 3.1e9}
SCOPES = ["blk/ssd/proj", "blk/ssd/prep", "blk/ssd/step", "blk/ssd/chunk",
          "blk/ssd/out", "blk/qkv", "blk/kv_scatter",
          "blk/attn/full/grouped_paged_attn", "blk/attn_out", "blk/ffn",
          "tick/embed", "tick/head"]


def test_the_readers_split_a_tick_by_the_programs_names(monkeypatch):
    doc = _synthetic(SCOPES)
    run, pt = _run_with(doc, real_config(), dict(FACTS))
    monkeypatch.setattr(pt, "load", lambda: doc)
    read = lambda name: loader.load_module("layer_metrics", name).read(run)
    want = {"served.tick_device_ms_p50": 30.0,
            "served.dense_ms_per_tick": 12.0,
            "served.head_sample_ms_per_tick": 4.0,
            "state.step_ms_per_tick": 2.0, "state.chunk_ms_per_tick": 2.0,
            "state.prep_ms_per_tick": 2.0, "attn.full_ms_per_tick": 2.0,
            "pool.live_state_slots_pct": 97.5,
            "pool.live_kv_pct.backlog": 47.0,
            "served.tokens_per_s_slice_p50": 4000.0,
            "served.prefill_tokens_per_tick": 0.2 * 256,
            "served.decode_rows_per_tick": 80.0}
    for name, value in want.items():
        assert read(name) == pytest.approx(value), name
    # the parts and what no name covers add up to the tick
    assert sum(read(n) for n in PARTS) == pytest.approx(30.0)
    peak = yardstick.chip_peak("TPU v5 lite")
    c = real_config()
    assert read("state.step_hbm_roofline_pct") == pytest.approx(
        100 * ys.least_ms(ys.step_flops(c, 79.0), ys.step_bytes(c, 79.0),
                          peak) / 2.0)
    shape = {"live": 79.0, "chunk": 50.0, "chunk_rows": 0.2, "sampled": 80.0,
             "decode_keys": 79 * 660.0, "chunk_keys": 120.0,
             "chunk_pairs": 15000.0}
    assert read("attn.full_roofline_pct") == pytest.approx(
        100 * ys.least_ms(ys.attention_flops(c, 79 * 660.0 + 15000.0),
                          ys.attention_bytes(c, 79 * 660.0 + 120.0), peak)
        / 2.0)                          # four grouped heads: its own floor
    assert read("served.tick_hbm_roofline_pct") == pytest.approx(
        100 * ys.tick_bytes(c, shape) / peak.hbm_bytes_per_s * 1e3 / 30.0)
    assert read("served.tick_mfu_pct") == pytest.approx(
        100 * ys.tick_flops(c, shape) / 30e-3 / peak.bf16_flops)
    for name in SHARES:
        assert 0 < read(name), name
    assert sorted(NEW) == sorted(
        f[:-3] for f in os.listdir(os.path.join(loader.HERE,
                                                "layer_metrics"))
        if f[:-3] in NEW)
    # the other served families' helpers do not read this tick: at most one
    # answers, and ``_served`` finds this one
    for other in ("_dots3_trace", "_dsv2_trace", "_olmoh_trace",
                  "_ling3_trace"):
        assert loader.load_module("layer_metrics", other).parts_ms(run) \
            is None, other
    assert loader.load_module("layer_metrics", "_served").trace_of(run) \
        is loader.load_module("layer_metrics", "_falcon_h1_trace")


def test_the_readers_find_nothing_in_a_program_without_the_model(
        monkeypatch):
    """A served GPT's tick names ``blk/attn`` and ``blk/ffn`` and no
    ``blk/ssd/step``, and its family's facts hold no state rows: every
    reader of the trace returns ``None`` and raises nothing; so with no
    trace at all. The hybrid's tick (``blk/gdn/step``) is not this
    helper's either (the folded readers read that tick as Olmo-Hybrid's,
    and with a GPT run's facts find no tick shape: test_pb_fold.py)."""
    gpt = loader.load_json(loader.root_file(
        "perfbench/configs/gpt3-1.3b-serve.json"))
    for scopes in (["blk/qkv", "blk/attn", "blk/ffn", "tick/head"],
                   ["blk/gdn/step", "blk/attn", "blk/ffn", "tick/head"]):
        doc = _synthetic(scopes)
        run, pt = _run_with(doc, gpt, {
            "decode_rows_per_tick": 9.0, "prefill_rows_per_tick": 0.25,
            "prefill_chunk": 32, "live_kv_share": 0.5})
        monkeypatch.setattr(pt, "load", lambda doc=doc: doc)
        assert loader.load_module(
            "layer_metrics", "_falcon_h1_trace").parts_ms(run) is None
        names = ("served.tick_device_ms_p50",) + PARTS + SHARES \
            + COUNTED[:1] if "blk/gdn/step" not in scopes else SHARES
        for name in names:
            assert loader.load_module("layer_metrics", name).read(run) \
                is None, name
    run["ctx"].trace_doc = None
    assert loader.load_module(
        "layer_metrics", "served.tick_mfu_pct").read(run) is None


def test_the_cells_lists_name_the_new_metrics_of_this_cell(bench):
    cell = loader.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    assert cell["cell"]["chips"] == 1 \
        and cell["cell"]["traffic"] == "gen-640-backlog"
    for m in bench["per_layer"]:
        if m["name"] in NEW:    # a quantity another cell reports too
            assert CELL in m["workloads"] and len(m["workloads"]) > 1 \
                and m["moves"] == "serve_tokens_per_s", m["name"]
        else:       # no other metric's list of cells names this cell
            assert CELL not in m.get("workloads", ())
    # the cell's own copies went with the fold (PR 56)
    assert not [m["name"] for m in bench["per_layer"]
                if m["name"].startswith(("fh1.", "ssd."))]
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] in NEW}
    assert layers == {
        "serving tick (device)", "serving scheduler (host)",
        "paged attention / page pool",
        "recurrent state pool: step, chunked scan, in-place pass"}


# --- the check, controls included, through check() itself -------------------
@pytest.fixture(scope="module")
def served():
    """A toy engine that served four requests and still decodes two, and
    what ``check`` is handed: the context, the plan and a drive."""
    import paddle_tpu as paddle
    from paddle_tpu.models.falcon_h1 import FalconH1
    from paddle_tpu.serving import ServingConfig, ServingEngine

    fam = loader.load_module("families", "falcon_h1_serve")
    toy = dict(toy_config(), family="falcon_h1_serve",
               initializer_range=0.2)
    tables = {"published": {k: toy[k] for k in fam.PUBLISHED if k in toy},
              "cut": {k: toy["published"][k] for k in fam.CUT},
              "floors": {"num_hidden_layers": 2, "vocab_size": 24}}
    paddle.seed(5)
    net = FalconH1(fam.model_config(toy, **tables))
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
        eng=eng, rid_of=dict(enumerate(rids)),
        output=lambda i: np.asarray(eng.tokens_so_far(rids[i]), np.int32))
    ctx = types.SimpleNamespace(config=toy, seed=2 ** 31 + 3)
    return ctx, eng, {"requests": requests}, drive, finished


#: the fixture serves float32, which the reference repeats but for the
#: order of its sums: the shipped limits are bf16's at the published widths
FLOAT32_LIMITS = (0.02, 0.01, 0.002, 0.001, 0.001)


def test_the_check_passes_what_the_engine_served(served):
    ctx, eng, plan, drive, finished = served
    chk = loader.load_module("checks", "falcon_h1_serve")
    live = chk.still_decoding(ctx, plan, drive, finished)
    assert 1 <= len(live) <= chk.SAMPLE
    assert all(i not in finished for i, _, _ in live)
    verdict = chk.check(ctx, eng.served_weights(), plan, drive, finished,
                        limits=FLOAT32_LIMITS)
    assert verdict["ok"], verdict["note"]
    assert verdict["note"].count("allowed") == 6
    assert "slots still decoding" in verdict["note"]
    assert not chk.check(ctx, eng.served_weights(), plan, drive, [])["ok"]
    # with nothing still decoding there is no state to compare
    assert not chk.check(ctx, eng.served_weights(), plan, drive,
                         list(range(6)))["ok"]
    with pytest.raises(ValueError, match="unknown control"):
        chk.check(ctx, eng.served_weights(), plan, drive, finished,
                  control="rope")


@pytest.mark.parametrize("control", [
    "fp8", "bf16_state", "bf16_step", "no_ssm_out_multiplier",
    "no_key_multiplier", "state_not_carried", "conv_history_dropped",
    "conv_bias_dropped"])
def test_a_control_comes_out_not_correct(served, control):
    """A state kept in bf16, a step accumulated in bf16 and a dropped
    multiplier (the mixer's way out; the keys') each fail a limit, as do the
    others: the comparison tells each wrong model from the served one."""
    ctx, eng, plan, drive, finished = served
    chk = loader.load_module("checks", "falcon_h1_serve")
    verdict = chk.check(ctx, eng.served_weights(), plan, drive, finished,
                        control=control, limits=FLOAT32_LIMITS)
    assert not verdict["ok"], verdict["note"]
    assert f"[{control}]" in verdict["note"]


# --- the cell, rehearsed on the CPU ------------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory, bench):
    dst = tmp_path_factory.mktemp("checkout_falcon_h1")
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
            m["workloads"].append("toy-falcon-h1-cell")
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def rehearse(copy, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(copy), loader.ROOT])}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_rehearsal.py"), "1",
         "--workload", "toy-falcon-h1-cell", "--seed", str(2 ** 31 + 11),
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
    assert "slots still decoding" in out


def test_a_traced_rehearsal_reports_what_the_cpu_can(copy):
    """The CPU's trace has no device plane: the device readers return
    nothing, the counters and the scheduler's readers report."""
    line, out = rehearse(copy, 1)
    assert line["correct"] is True, out[-2000:]
    got = set(line["metrics"])
    # the engine's own record of its ticks reads on the CPU too
    assert set(HOLDS) <= got
    assert {"pool.live_state_slots_pct", "pool.live_kv_pct.backlog",
            "served.tokens_per_s_slice_p50", "served.prefill_tokens_per_tick",
            "served.decode_rows_per_tick"} <= got
    assert 0 < line["metrics"]["pool.live_state_slots_pct"]["value"] <= 100
