"""Ling-3.0-flash's cell: the configuration file against the catalog's row and
its family's ``check_widths``, the toy family through the contract's rules,
``yardstick_ling3``'s counts by hand, the new readers on a synthetic trace,
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

from perfbench import loader, yardstick, yardstick_ling3 as yl

from test_pb_contract import BACKLOG_HOLDS as HOLDS, config_file_is_sound, \
    family_is_only_a_model

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_ling3")
CELL = "serve-ling3-longgen-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# The cell's 25 quantities under the names they carry since PR 56: 23 of them
# are entries other cells report too (``served.*``, ``moe.tick_*``,
# ``latent.*``, ``mla.dense_decode``, ``pool.*`` since PR 53, when the cell's
# own ``ling.*`` copies and ``kda.prep_ms_per_tick`` went; the step and the
# pass before it under ``state.*`` since PR 56, whatever the rule), two are
# its own.
PARTS = ("served.dense_ms_per_tick", "served.head_sample_ms_per_tick",
         "state.step_ms_per_tick", "state.prep_ms_per_tick",
         "mla.dense_decode_ms_per_tick", "latent.scatter_ms_per_tick",
         "moe.tick_route_ms_per_tick", "moe.tick_experts_ms_per_tick",
         "moe.tick_shared_ms_per_tick", "served.unscoped_ms_per_tick")
SHARES = ("served.tick_mfu_pct", "served.tick_hbm_roofline_pct",
          "state.step_hbm_roofline_pct", "ling.mla_decode_roofline_pct",
          "moe.tick_experts_hbm_roofline_pct")
COUNTED = ("served.host_ms_per_tick", "moe.tick_expert_load_max_over_mean",
           "moe.tick_experts_touched_pct", "moe.tick_group_hit_pct",
           "pool.live_latent_pct", "pool.live_state_slots_pct",
           "served.decode_rows_per_tick", "served.tokens_per_s_slice_p50",
           "ling.warm_prefill_tokens_per_s")
NEW = ("served.tick_device_ms_p50",) + PARTS + SHARES + COUNTED
#: the entries that list this cell alone: what only this tick has (the
#: decode rows' attention alone over its own roofline, where
#: ``mla.dense_attn_roofline_pct`` is DeepSeek-V2's chunk and decode calls
#: together; the chunk path's reading from warm-in)
OWN = ("ling.mla_decode_roofline_pct", "ling.warm_prefill_tokens_per_s")
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "moe_shared_expert_intermediate_size", "num_attention_heads",
          "head_dim", "short_conv_kernel_size", "kv_lora_rank",
          "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
          "num_experts_per_tok", "n_group", "topk_group",
          "routed_scaling_factor", "rope_theta", "kda_lower_bound",
          "layer_group_size")


def real_config():
    return loader.load_json(loader.root_file(
        "perfbench/configs/ling-3.0-flash-serve.json"))


def toy_config():
    return loader.load_json(os.path.join(TOY, "configs", "toy-ling3.json"))


@pytest.fixture(scope="module")
def bench():
    return loader.load_json(loader.root_file("BENCHMARK.json"))


# --- the configuration -----------------------------------------------------
def test_the_configuration_is_the_catalogs_row_cut_in_three_keys(bench):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    c = real_config()
    entry = next(e for e in bench["configs"]
                 if e["name"] == "ling-3.0-flash-serve")
    assert entry["source"].startswith(row["source_url"] + "; cut: ")
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c[key] != value and c["published"][key] == value
        else:
            assert c[key] == value, key     # nested groups whole
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) \
        == (7, 128, 39296) and c["published"]["chips_a_layer"] == 4
    assert c["vocab_size"] * 4 == row["config"]["vocab_size"]
    for said in ("layers", "block", "kda", "A_log_dt_bias", "mla", "router",
                 "expert_bias", "swiglu_limit", "heads"):
        assert said in c["assumed"]
    assert set(c["omitted"]) == {"mtp", "training"}
    config_file_is_sound(entry, c)
    e = c["engine"]
    assert (e["num_slots"], e["page_size"], e["pages_per_slot"]) \
        == (64, 128, 133) and not e["prefix_cache"]


@pytest.mark.parametrize("key", WIDTHS)
def test_a_changed_width_is_refused_by_its_key(key):
    fam = loader.load_module("families", "ling3_serve")
    c = real_config()
    fam.check_widths(c)
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        fam.check_widths({**c, key: c[key] * 2})


@pytest.mark.parametrize("case,change,words", [
    ("no period", {"layers_held": [1, 2, 3, 4, 5, 6],
                   "num_hidden_layers": 6}, "whole periods"),
    ("no dense layer", {"layers_held": [2, 3, 4, 5, 6, 7, 8]},
     "one leading dense layer"),
    ("a clamped layer", {"layers_held": [1] + list(range(30, 36))},
     "whole periods|clamp"),
    ("half a group", {"experts_held": [0, 96], "num_experts": 96},
     "whole groups"),
    ("another score", {"score_function": "softmax"}, "written for"),
    ("a tied head", {"tie_word_embeddings": True}, "written for"),
])
def test_a_cut_or_a_form_the_family_is_not_written_for_is_refused(
        case, change, words):
    fam = loader.load_module("families", "ling3_serve")
    with pytest.raises(ValueError, match=words):
        fam.check_widths({**real_config(), **change})


def test_a_clamped_layer_is_refused_by_the_lists_name():
    fam = loader.load_module("families", "ling3_serve")
    c = real_config()
    clamp = list(c["share_expert_swiglu_limit_list"])
    clamp[7] = 5
    with pytest.raises(ValueError, match="share_expert_swiglu_limit_list"):
        fam.check_widths({**c, "share_expert_swiglu_limit_list": clamp})


def test_the_family_builds_the_model_from_the_files_sizes():
    fam = loader.load_module("families", "ling3_serve")
    cfg = fam.model_config(real_config())
    assert cfg.num_hidden_layers == 7 and cfg.num_experts == 512
    assert cfg.layer_kinds == ("kda",) * 4 + ("mla", "kda", "kda")
    assert [cfg.is_moe(i) for i in range(7)] == [False] + [True] * 6
    assert cfg.held == (0, 128) and cfg.vocab_size == 39296
    assert cfg.select_bias_range == 0.02 and cfg.rope_theta == 6e6
    assert round(cfg.num_params() / 1e9, 2) == 5.23
    # the yardstick counts the matrices and leaves norms, biases and the
    # gates' vectors out: within a thousandth
    assert abs(cfg.num_params() - yl.total_params(real_config())) \
        < 1e-3 * cfg.num_params()
    assert fam.limits(real_config()) == {
        "vocab_size": 39296, "num_slots": 64, "capacity": 17024}
    assert fam.PREFILL_CHUNK == 256
    from paddle_tpu.models.ling3 import TICK_STATS
    assert fam.STATS == TICK_STATS


def test_the_traffic_is_issue_49s_and_fits_a_slot():
    traffic = loader.load_data("traffic", "longgen-12k-backlog")
    gen = loader.load_module("generators", traffic["generator"])
    fam = loader.load_module("families", "ling3_serve")
    assert traffic["requests"] == 256 and traffic["cycle"] == 2
    assert traffic["prompt"] == {"median": 1024, "sigma": 0.5, "lo": 256,
                                 "hi": 4096}
    assert traffic["output"] == {"median": 12288, "sigma": 0.35,
                                 "lo": 8192, "hi": 24576}
    assert traffic["warm_in_s"] == 20 and traffic["slices"] == 9
    plan = gen.generate(traffic, 2 ** 31 + 5, 45.0, fam.limits(real_config()))
    sizes = {(len(r["prompt"]), r["max_new"]) for r in plan["requests"]}
    assert {p for p, _ in sizes} == {731, 1435}
    assert {o for _, o in sizes} == {9704, 15560}
    assert all(p + o <= 17024 for p, o in sizes)
    assert plan["mode"] == "closed" and len(plan["requests"]) == 256
    assert all(r["prompt"].max() < 39296 for r in plan["requests"][:4])


def test_the_benchmarks_reference_is_the_programs_copy():
    def body(path):
        with open(loader.root_file(path), encoding="utf-8") as f:
            text = f.read()
        return text[text.index("With ``N(.)``"):]

    mine = body("perfbench/references/ling3.py")
    assert mine == body("paddle_tpu/models/ling3_reference.py")
    assert "import paddle_tpu" not in mine and "from paddle_tpu" not in mine
    src = open(loader.root_file("perfbench/yardstick_ling3.py")).read()
    assert "paddle_tpu" not in src.split('"""')[2]
    chk = loader.load_module("checks", "ling3_serve")
    ref = loader.load_module("references", "ling3")
    assert chk.CONTROLS == (None, "fp8") + ref.CONTROLS[1:]


# --- the toy family, through the contract's rules ---------------------------
@pytest.fixture
def with_toy(tmp_path):
    dst, real = str(tmp_path / "perfbench"), loader.HERE
    shutil.copytree(real, dst, ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("families", "checks"):
        shutil.copy(os.path.join(TOY, kind, "toy_ling3.py"),
                    os.path.join(dst, kind, "toy_ling3.py"))
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
                                        "toy_ling3.py"))
    family_is_only_a_model(os.path.join(with_toy, "families",
                                        "ling3_serve.py"))
    cfg = toy_config()
    config_file_is_sound({"name": "toy-ling3", "reduced": cfg["reduced"]},
                         cfg)
    real = loader.load_module("families", "ling3_serve")
    with pytest.raises(ValueError, match="hidden_size"):
        real.check_widths(cfg)          # the shipped family holds to 2,560


# --- the yardstick, against hand arithmetic --------------------------------
def test_the_yardstick_counts_what_issue_49_reckons():
    c = real_config()
    m = lambda n: round(n / 1e6, 1)                          # noqa: E731
    assert m(yl.kda_mixer_params(c)) == 63.0
    assert m(yl.mla_mixer_params(c)) == 32.0
    assert m(yl.expert_params(c)) == 5.9
    assert m(yl.held_params(c) / 6) == 755.0
    assert (yl.kda_layers(c), yl.mla_layers(c), yl.moe_layers(c)) \
        == (6, 1, 6)
    assert round(yl.total_params(c) / 1e9, 2) == 5.23
    assert yl.state_entries(c) * 4 == 2097152          # 2.10 MB a state
    peak = yardstick.chip_peak("TPU v5 lite")
    # 64 live rows: 6 x 64 x 4.24 MB = 1.63 GB, 2.0 ms at 819 GB/s
    moved = yl.step_bytes(c, 64.0)
    assert 1.61e9 < moved < 1.65e9
    assert 1.96 < yl.least_ms(yl.step_flops(c, 64.0), moved, peak) < 2.02
    # the held experts touched at 63 %: 6 x 0.63 x 1.51 GB = 5.7 GB
    assert 5.6e9 < yl.experts_bytes(c, 0.63) < 5.8e9
    # a tick of no rows reads every dense weight once, no expert, no head
    none = {"live": 0.0, "chunk": 0.0, "chunk_rows": 0.0, "sampled": 0.0,
            "decode": (0.0, 0.0), "chunk_attn": (0.0, 0.0), "touched": 0.0,
            "expert_rows": 0.0}
    assert yl.tick_bytes(c, none) == yl.dense_params(c) * 2
    assert 1.0e9 < yl.tick_bytes(c, none) < 1.2e9
    assert yl.tick_flops(c, none) == 0


def test_the_yardstick_on_a_hand_worked_tick():
    c = real_config()
    # 64 live rows at 4,000 keys each, no chunk, 63 % of the experts touched
    shape = {"live": 64.0, "chunk": 0.0, "chunk_rows": 0.0, "sampled": 64.0,
             "decode": (256000.0, 256000.0), "chunk_attn": (0.0, 0.0),
             "touched": 0.63, "expert_rows": 128.0}
    # the one MLA layer's latents: 1,152 B a key
    ops, moved = yl.attention_ops_bytes(c, (shape["decode"],))
    assert moved == 256000 * 1152
    assert ops == 2.0 * 32 * (576 + 512) * 256000       # absorbed: the lesser
    assert yl.tick_flops(c, shape) == pytest.approx(
        2.0 * yl.dense_params(c) * 64 + 2.0 * yl.expert_params(c) * 128 * 6
        + 2.0 * 2560 * 39296 * 64 + yl.step_flops(c, 64.0) + ops)
    peak = yardstick.chip_peak("TPU v5 lite")
    ms = yl.tick_bytes(c, shape) / peak.hbm_bytes_per_s * 1e3
    # ISSUE 49's reckoning: ~8.5 GB, 10.4 ms at the whole HBM roofline
    assert 10.0 < ms < 11.5
    assert yl.tick_bytes(c, shape) / peak.hbm_bytes_per_s \
        > 10 * yl.tick_flops(c, shape) / peak.bf16_flops


# --- the readers, on a synthetic trace ------------------------------------
def _op(name, scope, t0, dur):
    return {"name": name, "scope": scope, "start_ns": t0, "dur_ns": dur}


def _synthetic(scopes):
    """Two whole 30 ms runs of ``jit_tick`` on one device plane, each with
    one operation a scope, 2 ms long, and 2 ms under no scope."""
    ops, runs = [], []
    for r in range(2):
        t0 = r * 40_000_000
        runs.append({"name": "jit_tick(1)", "start_ns": t0,
                     "dur_ns": 30_000_000})
        for i, scope in enumerate(scopes):
            ops.append(_op(f"fusion.{i}", f"jit(tick)/{scope}/dot",
                           t0 + i * 2_000_000, 2_000_000))
        ops.append(_op("copy.1", "jit(tick)", t0 + 28_000_000, 2_000_000))
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": runs},
        {"name": "XLA Ops", "events": ops}]}]}


def _run_with(doc, config, facts):
    pt = loader.load_module("layer_metrics", "_program_trace")
    ctx = types.SimpleNamespace(
        trace_doc=doc, config=config,
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    return {"ctx": ctx, "facts": facts, "notes": []}, pt


FACTS = {"decode_rows_per_tick": 64.0, "prefill_rows_per_tick": 0.0,
         "prefill_chunk": 256, "live_kv_share": 0.25,
         "serve_tokens_per_s_slice_p50": 3000.0,
         "tick_live_state_rows": 64.0, "tick_chunk_tokens": 0.0,
         "tick_decode_keys": 64 * 4000.0, "tick_chunk_keys": 0.0,
         "tick_decode_pairs": 64 * 4000.0, "tick_chunk_pairs": 0.0,
         "tick_group_hit_share": 0.8, "tick_expert_rows": 128.0,
         "tick_expert_load_max_over_mean": 4.5,
         "tick_experts_touched_share": 0.6,
         "tick_held_rows_unaccounted": 0.0, "live_state_share": 1.0,
         "live_latent_share": 0.25, "state_bytes": 0.85e9,
         "warm_prefill_tokens_per_s": 9000.0}
SCOPES = ["blk/kda/proj", "blk/kda/prep", "blk/kda/step", "blk/kda/out",
          "blk/qkv", "blk/latent_scatter", "blk/mla/decode/latent_attn",
          "blk/attn_out", "blk/ffn", "blk/ffn/moe/route",
          "blk/ffn/moe/experts", "blk/ffn/moe/shared", "tick/embed",
          "tick/head"]


def test_the_readers_split_a_tick_by_the_programs_names(monkeypatch):
    doc = _synthetic(SCOPES)
    run, pt = _run_with(doc, real_config(), dict(FACTS))
    monkeypatch.setattr(pt, "load", lambda: doc)
    read = lambda name: loader.load_module("layer_metrics", name).read(run)
    want = {"served.tick_device_ms_p50": 30.0,
            "served.dense_ms_per_tick": 10.0,
            "served.head_sample_ms_per_tick": 4.0,
            "state.step_ms_per_tick": 2.0, "state.prep_ms_per_tick": 2.0,
            "mla.dense_decode_ms_per_tick": 2.0,
            "latent.scatter_ms_per_tick": 2.0,
            "moe.tick_route_ms_per_tick": 2.0,
            "moe.tick_experts_ms_per_tick": 2.0,
            "moe.tick_shared_ms_per_tick": 2.0,
            "served.unscoped_ms_per_tick": 2.0,
            "moe.tick_expert_load_max_over_mean": 4.5,
            "moe.tick_experts_touched_pct": 60.0,
            "moe.tick_group_hit_pct": 80.0, "pool.live_latent_pct": 25.0,
            "pool.live_state_slots_pct": 100.0,
            "served.decode_rows_per_tick": 64.0,
            "served.tokens_per_s_slice_p50": 3000.0,
            "ling.warm_prefill_tokens_per_s": 9000.0}
    for name, value in want.items():
        assert read(name) == pytest.approx(value), name
    # the parts and what no name covers add up to the tick
    assert sum(read(n) for n in PARTS) == pytest.approx(30.0)
    peak = yardstick.chip_peak("TPU v5 lite")
    c = real_config()
    assert read("state.step_hbm_roofline_pct") == pytest.approx(
        100 * yl.least_ms(yl.step_flops(c, 64.0), yl.step_bytes(c, 64.0),
                          peak) / 2.0)
    assert read("moe.tick_experts_hbm_roofline_pct") == pytest.approx(
        100 * yl.experts_bytes(c, 0.6) / peak.hbm_bytes_per_s * 1e3 / 2.0)
    for name in SHARES:
        assert 0 < read(name), name
    assert sorted(NEW) == sorted(
        f[:-3] for f in os.listdir(os.path.join(loader.HERE,
                                                "layer_metrics"))
        if f[:-3] in NEW)
    # the other served families' helpers do not read this tick, nor this
    # one theirs: at most one answers, and ``_served`` finds this one
    for other in ("_dots3_trace", "_dsv2_trace", "_olmoh_trace",
                  "_falcon_h1_trace"):
        assert loader.load_module("layer_metrics", other).parts_ms(run) \
            is None, other
    assert loader.load_module("layer_metrics", "_served").trace_of(run) \
        is loader.load_module("layer_metrics", "_ling3_trace")


def test_the_readers_find_nothing_in_a_program_without_the_model(
        monkeypatch):
    """A served GPT's tick names ``blk/attn`` and ``blk/ffn`` and no
    ``blk/kda/step``, and its family's facts hold no state rows: every
    reader of the device returns ``None`` and raises nothing; so with no
    trace at all; and a hybrid's tick under ``blk/gdn/step`` is not this
    helper's either: the readers of this cell's own mechanism find nothing
    there (the folded ones read that tick as Olmo-Hybrid's:
    test_pb_fold.py)."""
    gpt = loader.load_json(loader.root_file(
        "perfbench/configs/gpt3-1.3b-serve.json"))
    everything = ("served.tick_device_ms_p50",) + PARTS + SHARES \
        + COUNTED[1:4] + COUNTED[5:6] + COUNTED[8:]
    for scopes, names in (
            (["blk/qkv", "blk/attn", "blk/ffn", "tick/head"], everything),
            (["blk/gdn/proj", "blk/gdn/step", "blk/ffn"], OWN)):
        doc = _synthetic(scopes)
        run, pt = _run_with(doc, gpt, {
            "decode_rows_per_tick": 9.0, "prefill_rows_per_tick": 0.25,
            "prefill_chunk": 32, "live_kv_share": 0.5})
        monkeypatch.setattr(pt, "load", lambda doc=doc: doc)
        assert loader.load_module(
            "layer_metrics", "_ling3_trace").parts_ms(run) is None
        for name in names:
            assert loader.load_module("layer_metrics", name).read(run) \
                is None, name
    run["ctx"].trace_doc = None
    for name in ("served.tick_mfu_pct", "state.step_hbm_roofline_pct"):
        assert loader.load_module("layer_metrics", name).read(run) is None


def test_the_cells_lists_name_the_new_metrics_of_this_cell(bench):
    cell = loader.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | set(HOLDS) <= names and len(NEW) == 25
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    assert cell["cell"]["chips"] == 1 \
        and cell["cell"]["traffic"] == "longgen-12k-backlog"
    for m in bench["per_layer"]:
        if m["name"] in NEW + HOLDS:
            assert CELL in m["workloads"] \
                and m["moves"] == "serve_tokens_per_s"
        else:       # no other metric's list of cells names this cell
            assert CELL not in m.get("workloads", ())
        if m["name"] in OWN:        # what only this tick has
            assert m["workloads"] == [CELL]
        elif m["name"] in NEW:      # a quantity another cell reports too
            assert len(m["workloads"]) > 1, m["name"]
    # the cell's own copies went with the fold (PR 53): two names are left
    assert sorted(m["name"] for m in bench["per_layer"]
                  if m["name"].startswith("ling.")) == [
        "ling.mla_decode_roofline_pct", "ling.warm_prefill_tokens_per_s"]
    assert not [m["name"] for m in bench["per_layer"] if m["name"] in (
        "kda.prep_ms_per_tick", "gdn.prep_ms_per_tick",
        "kda.step_ms_per_tick", "kda.step_hbm_roofline_pct")]


# --- the check, controls included, through check() itself -------------------
@pytest.fixture(scope="module")
def served():
    """A toy engine that decodes four requests of two sizes, none to its
    end, and what ``check`` is handed: the context, the plan and a drive."""
    import paddle_tpu as paddle
    from paddle_tpu.models.ling3 import Ling3
    from paddle_tpu.profiler import registry
    from paddle_tpu.serving import ServingConfig, ServingEngine

    fam = loader.load_module("families", "ling3_serve")
    toy_fam_widths = {
        "hidden_size": 32, "intermediate_size": 48,
        "moe_intermediate_size": 16,
        "moe_shared_expert_intermediate_size": 16, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16}
    toy = dict(toy_config(), family="ling3_serve", initializer_range=0.2)
    paddle.seed(5)
    net = Ling3(fam.model_config(toy, toy_fam_widths))
    net.eval()
    e = toy["engine"]
    eng = ServingEngine(net, ServingConfig(
        num_slots=e["num_slots"], page_size=e["page_size"],
        pages_per_slot=e["pages_per_slot"], prefill_chunk=8,
        prefix_cache=False))
    rng = np.random.default_rng(9)
    requests = [{"prompt": rng.integers(0, 96, n, dtype=np.int32),
                 "max_new": 150, "due_s": 0.0} for n in (19, 41, 19, 41)]
    rids = [eng.submit(r["prompt"], r["max_new"]) for r in requests]
    while min(len(eng.tokens_so_far(rid)) for rid in rids) < 14:
        eng.step()
    eng.drain(0)
    drive = types.SimpleNamespace(
        eng=eng, reg=registry(), rid_of=dict(enumerate(rids)),
        output=lambda i: np.asarray(eng.tokens_so_far(rids[i]), np.int32))
    ctx = types.SimpleNamespace(config=toy, seed=2 ** 31 + 3)
    return ctx, eng, {"requests": requests}, drive, []


#: the fixture serves float32, which the reference repeats but for the
#: order of its sums: the shipped limits are bf16's at the published widths.
#: (margin, logit, route, the MLA layer's output, first state, deep state)
FLOAT32_LIMITS = (0.02, 0.01, 0.01, 0.002, 0.002, 0.002)


def test_the_check_passes_what_the_engine_served(served):
    ctx, eng, plan, drive, finished = served
    chk = loader.load_module("checks", "ling3_serve")
    live = chk.still_decoding(ctx, plan, drive, finished, 12)
    assert len(live) == chk.SAMPLE == 4
    assert {len(plan["requests"][i]["prompt"]) for i, _, _ in live} \
        == {19, 41}                     # both sizes
    assert live[0][2] == min(n for _, _, n in live)
    verdict = chk.check(ctx, eng.served_weights(), plan, drive, finished,
                        limits=FLOAT32_LIMITS, decoded=12)
    assert verdict["ok"], verdict["note"]
    assert verdict["note"].count("allowed") == 8
    assert "requests still decoding" in verdict["note"]
    # nothing has decoded as far as the shipped check compares
    assert not chk.check(ctx, eng.served_weights(), plan, drive, [])["ok"]
    # a request that finished is not read
    assert not chk.check(ctx, eng.served_weights(), plan, drive,
                         list(range(4)), decoded=12)["ok"]
    with pytest.raises(ValueError, match="unknown control"):
        chk.check(ctx, eng.served_weights(), plan, drive, [], control="x")


@pytest.mark.parametrize("control", [
    "fp8", "bf16_state", "unbounded_decay", "head_decay",
    "conv_history_dropped", "no_group_limit", "no_expert_bias",
    "not_renormalised", "no_routed_scaling", "no_rope", "no_head_gate"])
def test_a_control_comes_out_not_correct(served, control):
    ctx, eng, plan, drive, finished = served
    chk = loader.load_module("checks", "ling3_serve")
    verdict = chk.check(ctx, eng.served_weights(), plan, drive, finished,
                        control=control, limits=FLOAT32_LIMITS, decoded=12)
    assert not verdict["ok"], verdict["note"]
    assert f"[{control}]" in verdict["note"]


# --- the cell, rehearsed on the CPU ------------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory, bench):
    dst = tmp_path_factory.mktemp("checkout_ling3")
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
            m["workloads"].append("toy-ling3-cell")
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def rehearse(copy, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(copy), loader.ROOT])}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_rehearsal.py"), "1",
         "--workload", "toy-ling3-cell", "--seed", str(2 ** 31 + 11),
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
    assert "requests still decoding" in out


def test_a_traced_rehearsal_reports_what_the_cpu_can(copy):
    """The CPU's trace has no device plane: the device readers return
    nothing, the counters and the scheduler's readers report."""
    line, out = rehearse(copy, 1)
    assert line["correct"] is True, out[-2000:]
    got = set(line["metrics"])
    # the engine's own record of its ticks reads on the CPU too
    assert set(HOLDS) <= got
    assert set(COUNTED[1:]) <= got
    assert not got & (set(PARTS) | set(SHARES))
    assert 0 < line["metrics"]["pool.live_state_slots_pct"]["value"] <= 100
    assert line["metrics"]["ling.warm_prefill_tokens_per_s"]["value"] > 0
