"""GPT trained by ``HybridPipelineTrainer``, the program's normal entry
point for compiled training. The configuration file gives sizes, the mesh
and the storage types; every other knob of the trainer stays at the
program's default.
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import loader

WIDTHS = ("vocab_size", "hidden_size", "num_layers", "num_heads",
          "max_seq_len", "ffn_hidden_size")


def build(ctx, n_micro: int):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh
    from paddle_tpu.models import GPT, GPTConfig

    c, t = ctx.config, ctx.config["trainer"]
    paddle.seed(ctx.seed31)
    model = GPT(GPTConfig(**{k: c[k] for k in WIDTHS}))
    opt = paddle.optimizer.AdamW(t["learning_rate"],
                                 parameters=model.parameters())
    s = DistributedStrategy()
    s.amp = t["amp"]
    s.recompute = t["recompute"]
    axes = {"dp": 1, "pp": 1, "tp": 1, "sp": 1, **t["mesh"]}
    if int(np.prod(list(axes.values()))) != len(ctx.devices):
        raise ValueError(f"mesh {t['mesh']} is not the cell's "
                         f"{len(ctx.devices)} chips")
    mesh = create_mesh(axes, list(ctx.devices))
    tr = HybridPipelineTrainer(model, opt, s, mesh, n_micro=n_micro,
                               param_dtype=t["param_dtype"],
                               moment_dtype=t["moment_dtype"],
                               free_eager=t["free_eager"])
    return tr, opt


def run(ctx) -> dict:
    import jax

    gen = loader.load_module("generators", ctx.traffic["generator"])
    work = gen.generate(ctx.traffic, ctx.seed, ctx.seconds,
                        {"vocab_size": ctx.config["vocab_size"],
                         "max_seq_len": ctx.config["max_seq_len"]})
    tr, opt = build(ctx, work["n_micro"])
    built_peak = max(int((d.memory_stats() or {})
                         .get("peak_bytes_in_use", 0)) for d in ctx.devices)

    step_no = 0

    def one_step():
        nonlocal step_no
        with ctx.span("batch"):
            tokens = work["batch"](step_no)
        step_no += 1
        with ctx.span("step"):
            return float(jax.block_until_ready(tr.step(tokens)))

    warm = [one_step() for _ in range(ctx.traffic["warm_steps"])]

    t_open = ctx.open_window()
    losses, ends, first = [], [], 0
    traced = ctx.traffic["traced_steps"] if ctx.trace else 0
    if traced:
        ctx.start_trace()
    while time.perf_counter() - t_open < ctx.seconds:
        losses.append(one_step())
        ends.append(time.perf_counter())
        if traced and len(ends) == traced:
            # a traced step is slower, and stopping the profiler takes
            # time that is no step's: the rate is taken from here on
            ctx.stop_trace()
            traced, first, t_open = 0, len(ends), time.perf_counter()
    t_close = ends[-1]
    live = max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in ctx.devices)
    n_steps = len(ends) - first
    step_s = np.diff([t_open] + ends[first:])
    tokens_per_s = n_steps * work["tokens_per_step"] / (t_close - t_open)

    check = loader.load_module("checks", ctx.config["family"])
    verdict = check.check(ctx, tr, opt, work, warm + losses)
    compiles = ctx.compiles_in(ctx.t_open, t_close)
    return {
        "correct": verdict["ok"] and compiles == 0,
        "attempted": len(ends), "failed": 0,
        "end_to_end": {"train_tokens_per_s_per_chip":
                       tokens_per_s / len(ctx.devices)},
        "facts": {"tokens_per_s": tokens_per_s, "steps": n_steps,
                  "tokens_per_step": work["tokens_per_step"],
                  "seq": work["seq"], "micro": work["micro"],
                  "n_micro": work["n_micro"],
                  "step_s_p50": float(np.median(step_s)),
                  "traced_steps": ctx.traffic["traced_steps"],
                  "compiles_in_window": compiles,
                  "built_peak_bytes": built_peak, "live_bytes": live},
        "notes": [f"losses {warm[0]:.4f} -> {losses[-1]:.4f} over "
                  f"{len(warm) + len(losses)} steps; step p50 "
                  f"{np.median(step_s) * 1e3:.1f} ms; built peak "
                  f"{built_peak / 1e9:.2f} GB, in use between steps "
                  f"{live / 1e9:.2f} GB; {verdict['note']}"],
    }
