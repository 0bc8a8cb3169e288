"""The OLMoE cell's files: a CPU rehearsal of the family, its check and its
readers on a toy configuration in a temporary copy (as ``test_pb_run.py``
does for the GPT cells, whose fixture is left alone), ``yardstick_moe``'s
counts, and the ``moe.*`` readers on a synthetic trace."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from perfbench import loader, yardstick, yardstick_moe

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_olmoe")


def real_config():
    return loader.load_json(loader.root_file(
        "perfbench/configs/olmoe-1b-7b-train.json"))


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    dst = tmp_path_factory.mktemp("checkout_olmoe")
    shutil.copytree(os.path.join(loader.ROOT, "perfbench"),
                    dst / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("configs", "traffic"):
        for f in os.listdir(os.path.join(TOY, kind)):
            target = dst / "perfbench" / kind / f
            assert not target.exists()
            shutil.copy(os.path.join(TOY, kind, f), target)
    bench = loader.load_json(loader.root_file("BENCHMARK.json"))
    add = loader.load_json(os.path.join(TOY, "benchmark_entries.json"))
    bench["configs"] += add["configs"]
    bench["workloads"] += add["workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in add["joins"]:
            m["workloads"].append("toy-olmoe-cell")
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def rehearse(copy, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(copy), loader.ROOT])}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_rehearsal.py"), "1",
         "--workload", "toy-olmoe-cell", "--seed", str(2 ** 31 + 11),
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
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert "that step routed: dropped 0, assignments counted True" in out
    assert "dropped 0, rows moved" in out and "float32 reference" in out


def test_the_traced_rehearsal_reads_what_a_cpu_run_can(copy):
    """No device in a CPU trace: the ``*_ms_per_step`` readers and the
    roofline return nothing and are left out; the program's counter is
    there (``moe.train_mfu_pct`` is not joined: a CPU has no published
    peak, which is an error and not a default)."""
    line, out = rehearse(copy, 1)
    assert line["correct"] is True, out[-2000:]
    assert set(line["metrics"]) == {"proc.compiles_in_window",
                                    "moe.expert_load_max_over_mean"}
    assert line["metrics"]["proc.compiles_in_window"]["value"] == 0
    assert 1.0 <= line["metrics"]["moe.expert_load_max_over_mean"]["value"] \
        <= 8.0


def test_the_family_builds_olmoe_from_the_files_sizes():
    fam = loader.load_module("families", "olmoe_train")
    c = real_config()
    cfg = fam.model_config(c)
    assert (cfg.num_layers, cfg.moe_num_experts, cfg.moe_top_k,
            cfg.moe_expert_width, cfg.hidden_size) == (2, 64, 8, 1024, 2048)
    assert cfg.moe_dropless and (cfg.moe_aux_weight, cfg.moe_z_weight) == \
        (0.01, 0.001)
    assert fam.model_config({**c, "num_hidden_layers": 16}).num_layers == 16
    with pytest.raises(ValueError, match="multi-head"):
        fam.model_config({**c, "num_key_value_heads": 4})


def test_the_check_compares_every_layers_routing_with_the_references():
    import jax
    import jax.numpy as jnp

    chk = loader.load_module("checks", "olmoe_train")
    ref = loader.load_module("references", "olmoe")
    r = np.random.default_rng(3)
    t, h, f, e, k = 128, 32, 16, 8, 2
    w = {"mlp.gate": r.normal(size=(h, e)).astype(np.float32),
         "mlp.w_gate": r.normal(size=(e, h, f)).astype(np.float32) * 0.3,
         "mlp.w_up": r.normal(size=(e, h, f)).astype(np.float32) * 0.3,
         "mlp.w_down": r.normal(size=(e, f, h)).astype(np.float32) * 0.3}
    x = r.normal(size=(t, h)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        y, _, _, rows, ties = ref.moe(jnp.asarray(x), {
            n: jnp.asarray(v) for n, v in w.items()}, k, with_routing=True)
    want = {"x": x, "y": np.asarray(y), "rows": np.asarray(rows, np.int64),
            "near_ties": int(ties)}
    got = chk.layer_routing(w, want, k)
    assert got["ok"] and got["dropped"] == 0 and got["moved"] == 0 \
        and got["off"] == 0 and 1.0 <= got["load"] <= e
    # a reference that chose otherwise for ten tokens: rows moved, and the
    # tokens' outputs are off by far more than rounding
    other = dict(want, y=want["y"].copy(), rows=want["rows"].copy())
    other["y"][:10] *= 1.5
    other["rows"][[0, 1]] += (10, -10)
    bad = chk.layer_routing(w, other, k)
    assert not bad["ok"] and bad["moved"] == 10 and bad["off"] == 10
    assert chk.layer_routing(w, dict(other, near_ties=10), k)["ok"]
    assert chk.loss_agrees(11.4011, 11.4)[1]
    assert not chk.loss_agrees(11.4, 11.4 * (1 + 3.3e-4))[1]   # fp8 weights


def test_the_check_reads_what_the_step_itself_routed():
    """The step's own counts (``aux_stats``: two micro-batches of one
    sequence through two layers): an assignment without a row, or a step
    that counted other assignments than the batch holds, is seen."""
    chk = loader.load_module("checks", "olmoe_train")
    want = [{"rows": np.array([5, 3, 4, 4]), "near_ties": 1},
            {"rows": np.array([4, 4, 6, 2]), "near_ties": 0}]
    stats = {"moe/rows": np.array([18., 14., 20., 12.], np.float32),
             "moe/assigned": np.float32(64), "moe/load_max": np.float32(22)}
    got = chk.step_routing(stats, 64, 2, want)
    assert got == {"dropped": 0, "counted": True, "moved": 0,
                   "near_ties": 1}
    capped = dict(stats, **{"moe/rows": np.array([16., 14., 16., 12.])})
    assert chk.step_routing(capped, 64, 2, want)["dropped"] == 6
    top1 = dict(stats, **{"moe/assigned": np.float32(32)})
    assert not chk.step_routing(top1, 64, 2, want)["counted"]
    flipped = dict(stats, **{"moe/rows": np.array([20., 12., 20., 12.])})
    assert chk.step_routing(flipped, 64, 2, want)["moved"] == 1


def test_the_configuration_holds_the_catalog_rows_numbers():
    """Every number of the catalog row's ``config`` under the same key,
    but for the depth, which ``reduced`` names."""
    row = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 1024,
           "max_position_embeddings": 4096, "model_type": "olmoe",
           "norm_topk_prob": False, "num_attention_heads": 16,
           "num_experts": 64, "num_experts_per_tok": 8,
           "num_hidden_layers": 16, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "tie_word_embeddings": False, "vocab_size": 50304}
    c = real_config()
    differ = {k for k, v in row.items() if c.get(k, "absent") != v}
    assert differ == {"num_hidden_layers"} and differ <= set(c["reduced"])
    assert c["published"]["num_hidden_layers"] == row["num_hidden_layers"]
    gpt = loader.load_json(loader.root_file(
        "perfbench/configs/gpt3-1.3b-train.json"))
    assert c["trainer"] == gpt["trainer"]


def test_yardstick_moe_counts_olmoe():
    c = {**real_config(), "num_hidden_layers": 16}
    n = yardstick_moe.olmoe_params(c)
    # the model card: 6.92 B parameters, 1.3 B active with the embedding,
    # which a token reads and does not multiply: 1.18 B without
    assert round(n["total"] / 1e9, 2) == 6.92
    assert round(n["active"] / 1e9, 2) == 1.18
    assert round((n["active"] + 50304 * 2048) / 1e9, 2) == 1.28
    assert round(n["layer"] / 1e6, 1) == 419.6
    # 8 experts of 3 x 2048 x 1024 at 2 operations: 100.7 MFLOP a token
    ops, data = yardstick_moe.expert_ops_bytes(4096, c, backward=False)
    assert round(ops / 4096 / 1e6, 1) == 100.7
    full, _ = yardstick_moe.expert_ops_bytes(4096, c)
    assert full == 3 * ops
    # above the v5e's ridge of 240 FLOP/byte: compute bounds the experts
    peak = yardstick.chip_peak("TPU v5 lite")
    assert ops / data > peak.bf16_flops / peak.hbm_bytes_per_s
    flops = yardstick_moe.olmoe_train_flops_per_token(c, 4096)
    assert flops == 6.0 * n["active"] + 12.0 * 16 * 2048 * 4096
    # a step of 8 x 4096 tokens on 2 layers at the chip's peak: 100 %
    two = real_config()
    ops2, _ = yardstick_moe.expert_ops_bytes(4096, two)
    least_ms = ops2 / peak.bf16_flops * 2 * 8 * 1e3
    assert yardstick_moe.experts_roofline_pct(
        least_ms, 4096, 8, two, peak) == pytest.approx(100.0)
    assert yardstick_moe.experts_roofline_pct(
        2 * least_ms, 4096, 8, two, peak) == pytest.approx(50.0)


def synthetic_doc():
    """One chip, one run of ``jit_step_fn`` of 100 us with the expert
    layer's operations under their scope names, forward and backward; the
    grouped-matmul kernels carry XLA's own ``op_name``, as on the v5e."""
    def ev(name, start, dur, scope=""):
        return {"name": f"%{name} = bf16[8]{{0}} fusion(%p)",
                "start_ns": start, "dur_ns": dur, "scope": scope}

    base = "jit(step_fn)/jvp(fwd/blocks)/while/body/blk/ffn/"
    back = "jit(step_fn)/transpose(jvp(fwd/blocks))/while/body/blk/ffn/"
    ops = [ev("fusion.1", 0, 5_000, base + "moe/route/dot_general"),
           ev("fusion.2", 5_000, 10_000, base + "moe/dispatch/gather"),
           ev("ragged-dot-none.1", 15_000, 25_000, "ragged-dot-none"),
           ev("fusion.7", 40_000, 5_000, base + "moe/experts/mul"),
           ev("fusion.3", 45_000, 5_000, base + "moe/combine/gather"),
           ev("ragged-dot-none.2", 50_000, 20_000, "ragged-dot-none"),
           ev("fusion.4", 70_000, 10_000, back + "moe/dispatch/gather"),
           ev("fusion.5", 80_000, 10_000, base[:-4] + "attn/dot"),
           ev("fusion.6", 90_000, 10_000, "jit(step_fn)/opt/update/mul")]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            {"name": "jit_step_fn(1)", "start_ns": 0, "dur_ns": 100_000}]},
        {"name": "XLA Ops", "events": ops}]}]}


class FakeCtx:
    def __init__(self, config):
        self.trace_doc, self.config = {"planes": []}, config
        self.devices = [type("D", (), {"device_kind": "TPU v5 lite"})()]


def test_the_moe_readers_on_a_synthetic_trace(monkeypatch):
    pt = loader.load_module("layer_metrics", "_program_trace")
    doc = synthetic_doc()
    monkeypatch.setitem(pt._DOC, "doc", doc)
    config = real_config()
    run = {"ctx": FakeCtx(config),
           "facts": {"traced_steps": 1, "micro": 1, "seq": 4096,
                     "n_micro": 8, "tokens_per_s": 30000.0,
                     "moe_expert_load_max_over_mean": 1.17}}

    def read(name):
        return loader.load_module("layer_metrics", name).read(run)

    assert read("moe.route_ms_per_step") == pytest.approx(0.005)
    assert read("moe.dispatch_combine_ms_per_step") == pytest.approx(0.025)
    assert read("moe.experts_ms_per_step") == pytest.approx(0.050)
    assert read("moe.expert_load_max_over_mean") == 1.17
    peak = yardstick.chip_peak("TPU v5 lite")
    ops, _ = yardstick_moe.expert_ops_bytes(4096, config)
    assert read("moe.experts_roofline_pct") == pytest.approx(
        100.0 * ops / peak.bf16_flops * 16 / 0.050e-3)
    assert read("moe.train_mfu_pct") == pytest.approx(
        100.0 * 30000.0
        * yardstick_moe.olmoe_train_flops_per_token(config, 4096)
        / peak.bf16_flops)
    # the GPT readers see the expert layer's named parts as the block's,
    # and its kernels, which XLA leaves no scope name, as unscoped
    assert pt.read_step_part(run, "dense") == pytest.approx(0.045)
    assert pt.read_step_part(run, "unscoped") == pytest.approx(0.045)
    assert any("expert layer's parts" in n for n in run["notes"])


def test_the_moe_readers_return_nothing_where_nothing_is_named(monkeypatch):
    """The parent's program, or a dense GPT's step: no ``moe/`` scope."""
    pt = loader.load_module("layer_metrics", "_program_trace")
    doc = synthetic_doc()
    for ev in doc["planes"][0]["lines"][1]["events"]:
        ev["scope"] = ev["scope"].replace("moe/", "")
        ev["name"] = ev["name"].replace("ragged-dot-none", "fusion.9")
    monkeypatch.setitem(pt._DOC, "doc", doc)
    run = {"ctx": FakeCtx(real_config()), "facts": {"traced_steps": 1}}
    for name in ("moe.route_ms_per_step", "moe.experts_ms_per_step",
                 "moe.dispatch_combine_ms_per_step",
                 "moe.experts_roofline_pct", "moe.train_mfu_pct",
                 "moe.expert_load_max_over_mean"):
        assert loader.load_module("layer_metrics", name).read(run) is None
    untraced = {"ctx": FakeCtx(real_config()), "facts": {"traced_steps": 1}}
    untraced["ctx"].trace_doc = None
    assert loader.load_module(
        "layer_metrics", "moe.experts_ms_per_step").read(untraced) is None
