"""Olmo-Hybrid's cell: the configuration file against the catalog's row and
its family's ``check_widths``, the toy family through the contract's rules,
``yardstick_gdn``'s counts by hand, the new readers on a synthetic trace, the
check and its controls through ``check()`` itself at a small size, and a CPU
rehearsal of the cell on a toy configuration in a temporary copy."""
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import loader, yardstick, yardstick_gdn as yg

from test_pb_contract import BACKLOG_HOLDS as HOLDS, config_file_is_sound, \
    family_is_only_a_model

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_olmo_hybrid")
CELL = "serve-olmo-hybrid-gen-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARTS = ("served.dense_ms_per_tick", "served.head_sample_ms_per_tick",
         "state.step_ms_per_tick", "state.chunk_ms_per_tick",
         "state.prep_ms_per_tick", "attn.full_ms_per_tick",
         "served.unscoped_ms_per_tick")
SHARES = ("served.tick_mfu_pct", "served.tick_hbm_roofline_pct",
          "state.step_hbm_roofline_pct", "state.chunk_roofline_pct",
          "attn.full_roofline_pct")
COUNTED = ("pool.live_state_slots_pct", "served.tokens_per_s_slice_p50",
           "served.prefill_tokens_per_tick", "served.decode_rows_per_tick",
           "served.host_ms_per_tick")
#: with the holds of the judged window (PR 51; this cell's since PR 53)
NEW = ("served.tick_device_ms_p50",) + PARTS + SHARES + COUNTED + HOLDS
#: the entries that list this cell alone: none since PR 56 (a recurrent
#: state's passes are ``state.*`` whatever the rule, in Ling's and
#: Falcon-H1's cells too; full attention over K/V pages is Falcon-H1's too)
OWN = ()
WIDTHS = ("vocab_size", "hidden_size", "intermediate_size",
          "num_attention_heads", "num_key_value_heads",
          "linear_num_key_heads", "linear_num_value_heads",
          "linear_key_head_dim", "linear_value_head_dim",
          "linear_conv_kernel_dim", "max_position_embeddings")


def real_config():
    return loader.load_json(loader.root_file(
        "perfbench/configs/olmo-hybrid-7b-serve.json"))


def toy_config():
    return loader.load_json(os.path.join(TOY, "configs",
                                         "toy-olmo-hybrid.json"))


@pytest.fixture(scope="module")
def bench():
    return loader.load_json(loader.root_file("BENCHMARK.json"))


# --- the configuration -----------------------------------------------------
def test_the_configuration_is_the_catalogs_row_cut_in_one_key(bench):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    c = real_config()
    entry = next(e for e in bench["configs"]
                 if e["name"] == "olmo-hybrid-7b-serve")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c[key] != value and c["published"][key] == value
        else:
            assert c[key] == value, key     # nested groups whole
    assert c["num_hidden_layers"] == 16 and c["published"]["chips"] == 2
    for said in ("norms", "rope", "conv", "gates", "state", "l2norm"):
        assert said in c["assumed"]
    config_file_is_sound(entry, c)
    e = c["engine"]
    assert e["num_slots"] == 40 and e["pages_per_slot"] * e["page_size"] \
        == 1408 and e["prefix_cache"] is False


@pytest.mark.parametrize("key", WIDTHS)
def test_a_changed_width_is_refused_by_its_key(key):
    fam = loader.load_module("families", "olmo_hybrid_serve")
    c = real_config()
    fam.check_widths(c)
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        fam.check_widths({**c, key: c[key] * 2})


@pytest.mark.parametrize("layers", [15, 6])
def test_a_cut_that_is_no_whole_period_is_refused(layers):
    fam = loader.load_module("families", "olmo_hybrid_serve")
    with pytest.raises(ValueError, match="whole periods"):
        fam.check_widths({**real_config(), "num_hidden_layers": layers})


def test_the_family_builds_the_model_from_the_files_sizes():
    fam = loader.load_module("families", "olmo_hybrid_serve")
    cfg = fam.model_config(real_config())
    assert cfg.num_hidden_layers == 16
    assert cfg.layer_types.count("linear_attention") == 12
    assert cfg.layer_types[3::4] == ("full_attention",) * 4
    assert cfg.conv_width == 11520 and cfg.vocab_size == 100352
    assert round(cfg.num_params() / 1e9, 2) == 4.10
    assert cfg.num_params() == yg.total_params(real_config())
    assert fam.limits(real_config()) == {
        "vocab_size": 100352, "num_slots": 40, "capacity": 1408}
    assert fam.PREFILL_CHUNK == 256
    with pytest.raises(ValueError, match="untied head"):
        fam.model_config({**real_config(), "tie_word_embeddings": True})


def test_the_traffic_is_issue_44s_and_fits_a_slot():
    traffic = loader.load_data("traffic", "gen-512-backlog")
    gen = loader.load_module("generators", traffic["generator"])
    fam = loader.load_module("families", "olmo_hybrid_serve")
    assert traffic["requests"] == 800 and traffic["cycle"] == 2
    assert traffic["prompt"] == traffic["output"] == {
        "median": 512, "sigma": 0.35, "lo": 192, "hi": 1024}
    assert traffic["warm_in_s"] == 20 and traffic["slices"] == 9
    plan = gen.generate(traffic, 2 ** 31 + 5, 45.0, fam.limits(real_config()))
    sizes = {(len(r["prompt"]), r["max_new"]) for r in plan["requests"]}
    assert sizes == {(404, 648), (648, 404)}
    assert plan["mode"] == "closed" and len(plan["requests"]) == 800
    assert all(r["prompt"].max() < 100352 for r in plan["requests"][:4])


def test_the_benchmarks_reference_is_the_programs_copy():
    def body(path):
        with open(loader.root_file(path), encoding="utf-8") as f:
            text = f.read()
        return text[text.index("With ``N(.)``"):]

    mine = body("perfbench/references/olmo_hybrid.py")
    assert mine == body("paddle_tpu/models/olmo_hybrid_reference.py")
    assert "import paddle_tpu" not in mine and "from paddle_tpu" not in mine
    src = open(loader.root_file("perfbench/yardstick_gdn.py")).read()
    assert "paddle_tpu" not in src.split('"""')[2]


# --- the toy family, through the contract's rules ---------------------------
@pytest.fixture
def with_toy(tmp_path):
    dst, real = str(tmp_path / "perfbench"), loader.HERE
    shutil.copytree(real, dst, ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("families", "checks"):
        shutil.copy(os.path.join(TOY, kind, "toy_olmo_hybrid.py"),
                    os.path.join(dst, kind, "toy_olmo_hybrid.py"))
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
                                        "toy_olmo_hybrid.py"))
    family_is_only_a_model(os.path.join(with_toy, "families",
                                        "olmo_hybrid_serve.py"))
    cfg = toy_config()
    config_file_is_sound({"name": "toy-olmo-hybrid",
                          "reduced": cfg["reduced"]}, cfg)
    real = loader.load_module("families", "olmo_hybrid_serve")
    with pytest.raises(ValueError, match="vocab_size"):
        real.check_widths(cfg)          # the shipped family holds to 100,352


@pytest.mark.parametrize("key", WIDTHS)
def test_the_toy_family_refuses_each_changed_width_by_name(with_toy, key):
    cfg = toy_config()
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        config_file_is_sound(
            {"name": "toy-olmo-hybrid", "reduced": cfg["reduced"]},
            {**cfg, key: cfg[key] * 2})


# --- the yardstick, against hand arithmetic --------------------------------
def test_the_yardstick_counts_what_issue_44_reckons():
    c = real_config()
    m = lambda n: round(n / 1e6, 1)                          # noqa: E731
    assert m(yg.linear_mixer_params(c)) == 88.8
    assert m(yg.full_mixer_params(c)) == 59.0
    assert m(yg.ffn_params(c)) == 126.8
    assert yg.linear_layers(c) == 12 and yg.full_layers(c) == 4
    assert round(yg.total_params(c) / 1e9, 2) == 4.10
    assert yg.state_entries(c) * 4 == 2211840          # 2.21 MB a state
    peak = yardstick.chip_peak("TPU v5 lite")
    # 40 live rows: 12 x 40 x 4.42 MB = 2.12 GB, 2.6 ms at 819 GB/s
    moved = yg.step_bytes(c, 40.0)
    assert 2.12e9 < moved < 2.15e9
    assert 2.59 < yg.least_ms(yg.step_flops(c, 40.0), moved, peak) < 2.63
    # one read of the layers and the head: 7.43 GB, 9.1 ms
    shape = {"live": 0.0, "chunk": 0.0, "chunk_rows": 0.0, "sampled": 0.0,
             "decode_keys": 0.0, "chunk_keys": 0.0, "chunk_pairs": 0.0}
    assert 9.0 < yg.tick_bytes(c, shape) / peak.hbm_bytes_per_s * 1e3 < 9.2


def test_the_yardstick_on_a_hand_worked_tick():
    c = real_config()
    # 40 live rows at 700 keys each, a chunk of 256 behind 256 positions
    pairs = sum(257 + i for i in range(256))
    shape = {"live": 40.0, "chunk": 256.0, "chunk_rows": 1.0,
             "sampled": 40.0, "decode_keys": 28000.0, "chunk_keys": 512.0,
             "chunk_pairs": float(pairs)}
    # the full layers' K and V: 4 x 2 x 3,840 x 2 B = 61,440 B a key
    assert yg.attention_bytes(c, 28512.0) == 28512 * 61440
    assert yg.attention_flops(c, 1.0) == 4 * 4 * 3840
    # a chunk token a head: 3 x 2 x 96 x 192 + 2 x 64 x (96 + 192)
    assert yg.chunk_flops(c, 1.0) == 12 * 30 * (110592 + 36864)
    assert yg.chunk_bytes(c, 0.0, 1.0) == 12 * 2 * 2211840
    dense = 2.0 * yg.layer_params(c) * 296
    assert yg.tick_flops(c, shape) == pytest.approx(
        dense + 2.0 * 100352 * 3840 * 40 + yg.step_flops(c, 40.0)
        + yg.chunk_flops(c, 256.0)
        + yg.attention_flops(c, 28000.0 + pairs))
    # the tick multiplies 2.2 TFLOP and moves 11.5 GB: HBM binds it
    peak = yardstick.chip_peak("TPU v5 lite")
    assert 2.0e12 < yg.tick_flops(c, shape) < 2.4e12
    assert yg.tick_bytes(c, shape) / peak.hbm_bytes_per_s \
        > yg.tick_flops(c, shape) / peak.bf16_flops


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


FACTS = {"decode_rows_per_tick": 40.0, "prefill_rows_per_tick": 0.2,
         "prefill_chunk": 256, "live_kv_share": 0.5,
         "serve_tokens_per_s_slice_p50": 4000.0,
         "tick_live_state_rows": 39.0, "tick_chunk_tokens": 50.0,
         "tick_decode_keys": 39 * 700.0, "tick_chunk_keys": 120.0,
         "tick_chunk_pairs": 50 * 300.0, "live_state_share": 0.975,
         "state_bytes": 1.1e9}
SCOPES = ["blk/gdn/proj", "blk/state_io", "blk/gdn/prep", "blk/gdn/step",
          "blk/gdn/chunk", "blk/gdn/out", "blk/qkv", "blk/kv_scatter",
          "blk/attn/ragged_paged_attn", "blk/attn_out", "blk/ffn",
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
            "state.prep_ms_per_tick": 4.0, "attn.full_ms_per_tick": 2.0,
            "pool.live_state_slots_pct": 97.5,
            "served.tokens_per_s_slice_p50": 4000.0,
            "served.prefill_tokens_per_tick": 0.2 * 256,
            "served.decode_rows_per_tick": 40.0}
    for name, value in want.items():
        assert read(name) == pytest.approx(value), name
    # the parts and what no name covers add up to the tick
    assert sum(read(n) for n in PARTS) == pytest.approx(30.0)
    peak = yardstick.chip_peak("TPU v5 lite")
    c = real_config()
    assert read("state.step_hbm_roofline_pct") == pytest.approx(
        100 * yg.least_ms(yg.step_flops(c, 39.0), yg.step_bytes(c, 39.0),
                          peak) / 2.0)
    assert read("attn.full_roofline_pct") == pytest.approx(
        100 * yg.least_ms(yg.attention_flops(c, 39 * 700.0 + 15000.0),
                          yg.attention_bytes(c, 39 * 700.0 + 120.0), peak)
        / 2.0)                          # thirty heads: its own floor
    for name in SHARES:
        assert 0 < read(name), name
    assert sorted(NEW) == sorted(
        f[:-3] for f in os.listdir(os.path.join(loader.HERE,
                                                "layer_metrics"))
        if f[:-3] in NEW)


def test_the_readers_find_nothing_in_a_program_without_the_model(
        monkeypatch):
    """A served GPT's tick names ``blk/attn`` and ``blk/ffn`` and no
    ``blk/gdn/step``, and its family's facts hold no state rows: every
    reader but the scheduler's returns ``None`` and raises nothing; so with
    no trace at all."""
    doc = _synthetic(["blk/qkv", "blk/attn", "blk/ffn", "tick/head"])
    gpt = loader.load_json(loader.root_file(
        "perfbench/configs/gpt3-1.3b-serve.json"))
    run, pt = _run_with(doc, gpt, {
        "decode_rows_per_tick": 9.0, "prefill_rows_per_tick": 0.25,
        "prefill_chunk": 32, "live_kv_share": 0.5})
    monkeypatch.setattr(pt, "load", lambda: doc)
    for name in ("served.tick_device_ms_p50",) + PARTS + SHARES + COUNTED[:1]:
        assert loader.load_module("layer_metrics", name).read(run) is None, \
            name
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
        and cell["cell"]["traffic"] == "gen-512-backlog"
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] \
                and m["moves"] == "serve_tokens_per_s"
        else:       # no other metric's list of cells names this cell
            assert CELL not in m.get("workloads", ())
        if m["name"] in NEW:    # a quantity another cell reports too
            assert len(m["workloads"]) > 1, m["name"]
    assert not [m["name"] for m in bench["per_layer"]
                if m["name"].startswith("gdn.")]


# --- the check, controls included, through check() itself -------------------
@pytest.fixture(scope="module")
def served():
    """A toy engine that served four requests and still decodes two, and
    what ``check`` is handed: the context, the plan and a drive."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.olmo_hybrid import OlmoHybrid
    from paddle_tpu.serving import ServingConfig, ServingEngine

    fam = loader.load_module("families", "olmo_hybrid_serve")
    toy = dict(toy_config(), num_hidden_layers=6, family="olmo_hybrid_serve")
    widths = {k: toy[k] for k in fam.PUBLISHED}
    paddle.seed(5)
    net = OlmoHybrid(fam.model_config(toy, widths))
    net.eval()
    for block in net.blocks:            # decays above the chunked form's
        if not block.full:              # floor (tests/test_olmo_hybrid.py)
            a_log = block.mix.A_log.weight
            a_log._value = jnp.minimum(a_log._value, np.log(0.4))
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
FLOAT32_LIMITS = (0.02, 0.01, 0.002, 0.002)


def test_the_check_passes_what_the_engine_served(served):
    ctx, eng, plan, drive, finished = served
    chk = loader.load_module("checks", "olmo_hybrid_serve")
    live = chk.still_decoding(ctx, plan, drive, finished)
    assert 1 <= len(live) <= chk.SAMPLE
    assert all(i not in finished for i, _, _ in live)
    verdict = chk.check(ctx, eng.served_weights(), plan, drive, finished,
                        limits=FLOAT32_LIMITS)
    assert verdict["ok"], verdict["note"]
    assert verdict["note"].count("allowed") == 5
    assert "slots still decoding" in verdict["note"]
    assert not chk.check(ctx, eng.served_weights(), plan, drive, [])["ok"]
    # with nothing still decoding there is no state to compare
    assert not chk.check(ctx, eng.served_weights(), plan, drive,
                         list(range(6)))["ok"]


@pytest.mark.parametrize("control", [
    "fp8", "bf16_state", "state_not_carried", "no_decay",
    "beta_not_doubled", "conv_history_dropped", "rope"])
def test_a_control_comes_out_not_correct(served, control):
    ctx, eng, plan, drive, finished = served
    chk = loader.load_module("checks", "olmo_hybrid_serve")
    verdict = chk.check(ctx, eng.served_weights(), plan, drive, finished,
                        control=control, limits=FLOAT32_LIMITS)
    assert not verdict["ok"], verdict["note"]
    assert f"[{control}]" in verdict["note"]


# --- the cell, rehearsed on the CPU ------------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory, bench):
    dst = tmp_path_factory.mktemp("checkout_olmoh")
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
            m["workloads"].append("toy-olmo-hybrid-cell")
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def rehearse(copy, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(copy), loader.ROOT])}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_rehearsal.py"), "1",
         "--workload", "toy-olmo-hybrid-cell", "--seed", str(2 ** 31 + 11),
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
    assert {"pool.live_state_slots_pct", "served.tokens_per_s_slice_p50",
            "served.prefill_tokens_per_tick",
            "served.decode_rows_per_tick"} <= got
    assert 0 < line["metrics"]["pool.live_state_slots_pct"]["value"] <= 100
