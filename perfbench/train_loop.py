"""The training loop, once: a trainer stepped through one traffic plan, the
window from its opening to the end of its last step, the traced steps at its
head, and the facts the readers under ``layer_metrics/`` take.
``train_tokens_per_s_per_chip`` is defined here and nowhere else. What it
asks of a family:

``build(ctx, n_micro) -> (trainer, optimizer)``: the compiled trainer as the
    program's users get it; ``trainer.step(tokens)`` returns the loss
    (``hybrid_trainer`` here builds the program's one, for any model);
``limits(config) -> {"vocab_size", "max_seq_len"}``: what bounds a batch;
``facts_after(ctx, trainer) -> dict``, where the family has them: facts read
    off the trainer after the window's last step.
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import loader


def _memory(devices, key: str) -> int:
    """The fullest chip's reading of one of jax's memory statistics."""
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devices)


def hybrid_trainer(ctx, model, n_micro: int):
    """``model`` under ``HybridPipelineTrainer`` and AdamW, on the mesh and
    in the storage types of the configuration's ``trainer`` group."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh

    t = ctx.config["trainer"]
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


def run(ctx, build, limits,
        facts_after=lambda ctx, trainer: {}) -> dict:
    """One run of a training cell; a family binds its own functions."""
    import jax

    gen = loader.load_module("generators", ctx.traffic["generator"])
    work = gen.generate(ctx.traffic, ctx.seed, ctx.seconds,
                        limits(ctx.config))
    tr, opt = build(ctx, work["n_micro"])
    built_peak = _memory(ctx.devices, "peak_bytes_in_use")

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
    # what the family reads off the window's last step comes first: it
    # is that step's output
    more = facts_after(ctx, tr)
    live = _memory(ctx.devices, "bytes_in_use")
    peak = _memory(ctx.devices, "peak_bytes_in_use")
    n_steps = len(ends) - first
    step_s = np.diff([t_open] + ends[first:])
    tokens_per_s = n_steps * work["tokens_per_step"] / (t_close - t_open)

    check = loader.load_module("checks", ctx.config["family"])
    verdict = check.check(ctx, tr, opt, work, warm + losses)
    compiles = ctx.compiles_in(ctx.t_open, t_close)
    facts = {"tokens_per_s": tokens_per_s, "steps": n_steps,
             "tokens_per_step": work["tokens_per_step"],
             "seq": work["seq"], "micro": work["micro"],
             "n_micro": work["n_micro"],
             "step_s_p50": float(np.median(step_s)),
             "step_s_max": float(np.max(step_s)),
             "traced_steps": ctx.traffic["traced_steps"],
             "compiles_in_window": compiles,
             "built_peak_bytes": built_peak, "peak_bytes": peak,
             "live_bytes": live, **more}
    return {
        "correct": verdict["ok"] and compiles == 0,
        "attempted": len(ends), "failed": 0,
        "end_to_end": {"train_tokens_per_s_per_chip":
                       tokens_per_s / len(ctx.devices)},
        "facts": facts,
        "notes": [f"losses {warm[0]:.4f} -> {losses[-1]:.4f} over "
                  f"{len(warm) + len(losses)} steps; step p50 "
                  f"{np.median(step_s) * 1e3:.1f} ms, longest "
                  f"{np.max(step_s) * 1e3:.1f} (step "
                  f"{int(np.argmax(step_s))} of {n_steps}); built peak "
                  f"{built_peak / 1e9:.2f} GB, peak after the window "
                  f"{peak / 1e9:.2f} GB, in use between steps "
                  f"{live / 1e9:.2f} GB; {verdict['note']}"],
    }
