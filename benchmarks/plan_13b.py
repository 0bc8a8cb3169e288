"""GPT-3 13B hybrid-parallel memory/compile plan — the north-star proof.

BASELINE.md: the metric is tokens/sec/chip + MFU on GPT-3 1.3B-13B; the
north star is 13B hybrid-parallel (TP×PP×sharding) on v5p with ≥45% MFU.
This script proves the 13B end *compiles and fits*: it

  1. builds ``GPTConfig.gpt3_13b()`` under ``paddle.LazyGuard`` — every
     parameter is a ShapeDtypeStruct, so planning a 156 GB-state model
     materializes nothing on host or device;
  2. AOT-lowers + compiles the FULL hybrid train step (tp×pp×dp(ZeRO),
     remat, bf16 param/moment storage, fused flash attention, layer scan)
     through ``HybridPipelineTrainer.aot_compile`` on a virtual 16-device
     mesh for three candidate factorizations;
  3. records XLA's per-chip buffer-assignment accounting
     (``memory_analysis``: arguments − aliased + temps ≈ peak HBM) against
     the 95 GB v5p budget into ``BENCH_13B_PLAN.json``;
  4. (--dryrun) materializes a tiny-hidden, SAME-depth (40-layer) variant
     of the chosen plan and runs real steps, asserting the loss is finite
     and descending — the schedule/sharding path is executed, not only
     compiled.

Run on the CPU backend (no TPU needed):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=16 \
  python benchmarks/plan_13b.py [--dryrun]

Honesty notes recorded in the sidecar: the lowering is XLA:CPU SPMD (the
only backend this 1-chip environment can factorize 16 ways); TPU layouts
(8×128 tiling) can pad differently, and the CPU path promotes some bf16
boundaries to f32 (pipeline.py CPU workaround), which *overstates*
activation bytes — the budget check is conservative in that direction.
Reference-scale knobs this corresponds to:
/root/reference/paddle/fluid/framework/distributed_strategy.proto:25-35
(RecomputeConfig/ShardingConfig) — here they are strategy fields compiled
into one pjit program (SURVEY §7).
"""
import json
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V5P_HBM_GB = 95.0
SEQ = 2048
GLOBAL_BATCH = 32          # sequences per step (fill-drain over n_micro)


def rss_gb():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def plan_one_v5p(cfg, plan):
    """The definitive lowering: AOT-compile against a REAL v5p 2x4x2
    topology (jax.experimental.topologies — the actual TPU compiler and
    layouts, bf16 collectives, no CPU promotions). remat_policy='dots'
    because the pip-bundled libtpu miscompiles full-remat+scan flash
    ('Bad lhs type', see tests/test_tpu_lowering.py) — selective remat
    is the production bench config anyway."""
    import os
    import time as _t

    import jax
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5p:2x4x2")
    os.environ["PADDLE_TPU_TARGET_PLATFORM"] = "tpu"
    try:
        t0 = _t.time()
        plan = dict(plan, remat_policy="dots")
        _, _, trainer = build_trainer(cfg, plan, devices=topo.devices)
        batch = jax.ShapeDtypeStruct((GLOBAL_BATCH, SEQ), np.int32)
        ma = trainer.aot_compile(batch).memory_analysis()
        out = dict(plan)
        out["compile_s"] = round(_t.time() - t0, 1)
        out["host_peak_rss_gb"] = round(rss_gb(), 2)
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes"):
            out[k] = int(getattr(ma, k))
        peak = (out["argument_size_in_bytes"] - out["alias_size_in_bytes"]
                + out["temp_size_in_bytes"])
        out["peak_bytes_per_chip"] = int(peak)
        out["peak_gb_per_chip"] = round(peak / 1e9, 2)
        out["fits_v5p_95gb"] = bool(peak / 1e9 <= V5P_HBM_GB)
        out["hbm_headroom_gb"] = round(V5P_HBM_GB - peak / 1e9, 2)
        return out
    finally:
        del os.environ["PADDLE_TPU_TARGET_PLATFORM"]


def build_trainer(cfg, plan, abstract=True, devices=None):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.distributed_strategy import \
        DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.models.gpt import GPT

    strat = DistributedStrategy()
    strat.amp = True
    strat.recompute = True
    strat.hybrid_configs = {"dp_degree": plan["dp"],
                            "mp_degree": plan["tp"],
                            "pp_degree": plan["pp"]}
    if plan.get("zero", 0):
        strat.sharding = True
        strat.sharding_configs = {"sharding_stage": plan["zero"]}
    if abstract:
        with paddle.LazyGuard():
            model = GPT(cfg)
    else:
        model = GPT(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                 parameters=model.parameters())
    mesh = None
    if devices is not None:
        from paddle_tpu.distributed.strategy_compiler import \
            build_mesh_from_strategy

        n = plan["dp"] * plan["tp"] * plan["pp"]
        mesh = build_mesh_from_strategy(strat, np.array(devices)[:n])
    trainer = HybridPipelineTrainer(
        model, opt, strategy=strat, mesh=mesh, n_micro=plan["n_micro"],
        param_dtype="bfloat16", moment_dtype="bfloat16",
        remat_policy=plan.get("remat_policy"))
    return model, opt, trainer


def plan_one(cfg, plan):
    import jax
    t0 = time.time()
    _, _, trainer = build_trainer(cfg, plan)
    batch = jax.ShapeDtypeStruct((GLOBAL_BATCH, SEQ), np.int32)
    compiled = trainer.aot_compile(batch)
    ma = compiled.memory_analysis()
    out = dict(plan)
    out["compile_s"] = round(time.time() - t0, 1)
    out["host_peak_rss_gb"] = round(rss_gb(), 2)
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes"):
        out[k] = int(getattr(ma, k))
    peak = (out["argument_size_in_bytes"] - out["alias_size_in_bytes"]
            + out["temp_size_in_bytes"])
    out["peak_bytes_per_chip"] = int(peak)
    out["peak_gb_per_chip"] = round(peak / 1e9, 2)
    out["fits_v5p_95gb"] = bool(peak / 1e9 <= V5P_HBM_GB)
    out["hbm_headroom_gb"] = round(V5P_HBM_GB - peak / 1e9, 2)
    return out


def main():
    import jax
    from paddle_tpu.models.gpt import GPTConfig

    cfg = GPTConfig.gpt3_13b()
    n_dev = len(jax.devices())
    assert n_dev >= 16, f"need 16 virtual devices, got {n_dev}"

    plans = [
        # tp inside the attention/ffn shards the big matmuls (MXU-friendly
        # 5120/8=640 cols); pp=2 keeps bubble small at n_micro=8
        {"name": "A_tp8_pp2", "tp": 8, "pp": 2, "dp": 1, "zero": 0,
         "n_micro": 8},
        # deeper pipeline, narrower tp: less tp collective traffic,
        # bigger bubble; 40/4=10 layers per stage
        {"name": "B_tp4_pp4", "tp": 4, "pp": 4, "dp": 1, "zero": 0,
         "n_micro": 16},
        # dp=2 with ZeRO-2: moments sharded over dp — the
        # sharding-stage2 leg of the north-star config
        {"name": "C_tp4_pp2_dp2_zero2", "tp": 4, "pp": 2, "dp": 2,
         "zero": 2, "n_micro": 8},
    ]

    results = {"model": "gpt3_13b",
               "hidden": cfg.hidden_size, "layers": cfg.num_layers,
               "heads": cfg.num_heads, "seq": SEQ,
               "vocab": cfg.vocab_size,
               "params_b": round(cfg.num_params() / 1e9, 2),
               "global_batch": GLOBAL_BATCH,
               "n_virtual_devices": n_dev,
               "budget_gb_per_chip": V5P_HBM_GB,
               "storage": "bf16 params + bf16 AdamW moments, f32 update "
                          "math (r3-validated: LOSSCURVE_r03 0.17% rel)",
               "lowering_backend": jax.default_backend(),
               "notes": [
                   "abstract LazyGuard init: zero parameter bytes "
                   "materialized (see host_peak_rss_gb per plan)",
                   "XLA:CPU SPMD lowering; TPU 8x128 layouts may pad "
                   "differently; CPU f32 boundary promotions overstate "
                   "activation bytes (conservative for the budget check)",
               ],
               "plans": []}

    for plan in plans:
        print(f"--- planning {plan['name']} ...", flush=True)
        try:
            r = plan_one(cfg, plan)
        except Exception as e:  # record failures honestly
            r = dict(plan)
            r["error"] = f"{type(e).__name__}: {e}"[:500]
        results["plans"].append(r)
        print(json.dumps(r), flush=True)

    # definitive stage: the REAL v5p compiler + layouts (available
    # offline via jax.experimental.topologies) — the CPU plans above are
    # kept as the comparison proxy
    results["plans_v5p_true_lowering"] = []
    for plan in plans:
        print(f"--- v5p-true lowering {plan['name']} ...", flush=True)
        try:
            r = plan_one_v5p(cfg, plan)
        except Exception as e:
            r = dict(plan)
            r["error"] = f"{type(e).__name__}: {e}"[:500]
        results["plans_v5p_true_lowering"].append(r)
        print(json.dumps(r), flush=True)

    pool = [r for r in results["plans_v5p_true_lowering"]
            if r.get("fits_v5p_95gb")] or \
        [r for r in results["plans"] if r.get("fits_v5p_95gb")]
    if pool:
        chosen = min(pool, key=lambda r: r["peak_bytes_per_chip"])
        results["chosen"] = chosen["name"]
        results["chosen_rationale"] = (
            "chosen from the v5p TRUE lowerings when available (real TPU "
            "layouts); all fitting plans are throughput-equivalent until "
            "measured on hardware — lowest per-chip peak wins (most "
            "activation headroom to raise n_micro/batch toward the MFU "
            "target)")

    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_13B_PLAN.json")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    print("wrote", out_path)

    if "--dryrun" in sys.argv:
        dryrun(results)


def dryrun(results):
    """Tiny-hidden, full-depth (40-layer) variant of the chosen plan,
    actually executed: 3 steps, loss finite and descending."""
    import jax
    from paddle_tpu.models.gpt import GPTConfig

    name = results.get("chosen", "A_tp8_pp2")
    plan = next(p for p in results["plans"] if p["name"] == name)
    cfg = GPTConfig(hidden_size=128, num_layers=40, num_heads=8,
                    max_seq_len=128, vocab_size=512)
    model, opt, trainer = build_trainer(cfg, plan, abstract=False)
    rng = np.random.RandomState(0)
    bsz = plan["n_micro"] * plan["dp"]
    tok = rng.randint(0, cfg.vocab_size, (bsz, 128)).astype(np.int32)
    losses = [float(trainer.step(tok)) for _ in range(3)]
    print("dryrun losses:", losses)
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss not descending: {losses}"
    results["dryrun_40layer_tiny"] = {
        "plan": name, "hidden": cfg.hidden_size, "layers": cfg.num_layers,
        "losses": [round(l, 4) for l in losses], "descending": True}
    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_13B_PLAN.json")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    print("dryrun green; sidecar updated")


if __name__ == "__main__":
    main()
