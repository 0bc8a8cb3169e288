"""Train GPT-3 13B on a v5p-16 pod, consuming the validated plan verbatim.

The plan artifact (BENCH_13B_PLAN.json, produced by
benchmarks/plan_13b.py) records three TP x PP x ZeRO factorizations of
the FULL 13B hybrid step, AOT-compiled against a real v5p 2x4x2
topology with XLA's per-chip buffer accounting (42.0-62.4 GB/chip vs
the 95 GB budget). This example reads the chosen plan — default
``C_tp4_pp2_dp2_zero2`` — and builds exactly that trainer:

  tp=4, pp=2, dp=2 + ZeRO-2, n_micro=8, global batch 32 x seq 2048,
  bf16 params + bf16 AdamW moments (f32 update math), selective-dots
  rematerialization, fused flash attention + fused lm-head/CE,
  LinearWarmup -> cosine schedule.

On a machine with >= 16 TPU devices this trains from the same on-disk
corpus format as examples/train_gpt_1p3b_single_chip.py (flat int32
token file, strided-window zero-copy loader). Elsewhere,
``--validate`` executes the SAME plan on a virtual 16-device CPU mesh
with a tiny-hidden, same-depth (40-layer) model — the schedule,
shardings and collectives all run for real; only the widths shrink:

  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=16 \
  python examples/train_gpt_13b.py --validate

Reference anchor: the reference trains this class of model with the
fleet hybrid-parallel strategy chain
(distributed_strategy.proto:25-35 RecomputeConfig/ShardingConfig;
meta_optimizers/ pipeline + sharding + amp); here the same knobs are
strategy fields compiled into one pjit program (SURVEY §7).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

PLAN_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_13B_PLAN.json")


def load_plan(name):
    with open(PLAN_FILE) as f:
        doc = json.load(f)
    # prefer the true-TPU lowering record when present
    pools = doc.get("plans_v5p_true_lowering") or doc["plans"]
    for p in pools:
        if p["name"] == name:
            return doc, p
    names = [p["name"] for p in pools]
    raise SystemExit(f"plan {name!r} not in {PLAN_FILE} (have {names})")


def build(cfg, plan, sched_steps=2000):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.distributed_strategy import \
        DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.strategy_compiler import \
        build_mesh_from_strategy
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
    model = GPT(cfg)
    sched = paddle.optimizer.lr.LinearWarmup(
        paddle.optimizer.lr.CosineAnnealingDecay(1e-4,
                                                 T_max=sched_steps),
        warmup_steps=100, start_lr=1e-7, end_lr=1e-4)
    opt = paddle.optimizer.AdamW(sched, weight_decay=0.01,
                                 parameters=model.parameters())
    import jax

    need = plan["dp"] * plan["tp"] * plan["pp"]
    mesh = build_mesh_from_strategy(strat, jax.devices()[:need])
    trainer = HybridPipelineTrainer(
        model, opt, strategy=strat, mesh=mesh, n_micro=plan["n_micro"],
        param_dtype="bfloat16", moment_dtype="bfloat16",
        remat_policy=plan.get("remat_policy"))
    return trainer, sched


def main(argv):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig

    plan_name = "C_tp4_pp2_dp2_zero2"
    validate = "--validate" in argv
    steps = 50
    corpus = None
    for a in argv:
        if a.startswith("--plan="):
            plan_name = a.split("=", 1)[1]
        elif a.startswith("--steps="):
            steps = int(a.split("=", 1)[1])
        elif a.startswith("--corpus="):
            corpus = a.split("=", 1)[1]
    doc, plan = load_plan(plan_name)
    need = plan["dp"] * plan["tp"] * plan["pp"]
    have = jax.device_count()
    print(f"plan {plan['name']}: tp={plan['tp']} pp={plan['pp']} "
          f"dp={plan['dp']} zero={plan.get('zero', 0)} "
          f"n_micro={plan['n_micro']} "
          f"(validated peak {plan.get('peak_gb_per_chip', '?')} GB/chip "
          f"on v5p)")
    if have < need:
        raise SystemExit(
            f"this plan needs {need} devices; {have} visible. On a "
            f"v5p-16 pod run as-is; elsewhere run --validate under\n"
            f"  JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")

    paddle.seed(0)
    if validate and jax.devices()[0].platform == "cpu":
        # same DEPTH (40 layers), tiny widths: the schedule/shardings/
        # collectives execute for real on the 16-way virtual mesh
        cfg = GPTConfig(vocab_size=512, hidden_size=64,
                        num_layers=40, num_heads=4, max_seq_len=128)
        global_batch, seq = 16, 128
        steps = min(steps, 3)
    else:
        cfg = GPTConfig.gpt3_13b()
        global_batch, seq = doc["global_batch"], doc["seq"]
    trainer, sched = build(cfg, plan)

    loader = None
    if corpus:
        from paddle_tpu.io.native_engine import token_windows

        tokens = np.memmap(corpus, dtype=np.int32, mode="r")
        loader = token_windows(tokens, seq_len=seq,
                               batch_size=global_batch, shuffle=True,
                               seed=0, epochs=10**6, num_workers=2)
        def batches():
            while True:
                (w,) = next(loader)
                yield w[:, :seq].astype(np.int32)
        gen = batches()
    else:
        rng = np.random.RandomState(0)

        def batches():
            while True:
                yield rng.randint(0, cfg.vocab_size,
                                  (global_batch, seq)).astype(np.int32)
        gen = batches()

    losses = []
    try:
        for i in range(steps):
            toks = next(gen)
            t0 = time.perf_counter()
            loss = trainer.step(toks)
            loss_v = float(np.asarray(loss))
            sched.step()
            dt = time.perf_counter() - t0
            losses.append(loss_v)
            print(f"step {i}: loss {loss_v:.4f}  "
                  f"{global_batch * seq / dt:,.0f} tokens/s "
                  f"({dt*1e3:.0f} ms)", flush=True)
    finally:
        if loader is not None:
            loader.close()
    assert np.isfinite(losses).all()
    if len(losses) >= 3:
        assert losses[-1] < losses[0], losses
    print("ok: plan executed with descending loss")


if __name__ == "__main__":
    main(sys.argv[1:])
