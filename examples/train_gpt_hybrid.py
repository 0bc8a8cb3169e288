"""Hybrid-parallel GPT training (dp×tp×pp in ONE pjit program) with
sharded async checkpointing. Runs on the 8-device virtual CPU mesh or
real TPU slices unchanged."""
import os, sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax

if jax.default_backend() == "cpu" and len(jax.devices()) < 8:
    raise SystemExit("run with 8 virtual devices: "
                     "JAX_PLATFORMS=cpu "
                     "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                     "python examples/train_gpt_hybrid.py")

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import checkpoint as dck
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
from paddle_tpu.distributed.strategy_compiler import build_mesh_from_strategy
from paddle_tpu.models import GPT, GPTConfig


def main():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=4,
                    num_heads=4, max_seq_len=128)
    model = GPT(cfg)
    opt = paddle.optimizer.AdamW(
        3e-4, parameters=model.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))

    s = DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 2}
    s.amp = True
    s.sharding = True
    s.sharding_configs = {"sharding_stage": 2}
    mesh = build_mesh_from_strategy(s)
    trainer = HybridPipelineTrainer(model, opt, s, mesh, n_micro=2)

    rng = np.random.RandomState(0)
    with dck.CheckpointManager("/tmp/gpt_ckpt", keep=2) as mgr:
        for step in range(10):
            tokens = rng.randint(0, 512, (8, 128)).astype(np.int32)
            loss = trainer.step(tokens)
            if (step + 1) % 5 == 0:
                mgr.save(step + 1, trainer.device_state(),
                         meta={"step": step + 1})
            print(f"step {step}: loss {float(np.asarray(loss)):.4f}")


if __name__ == "__main__":
    main()
