"""OLMoE trained by ``HybridPipelineTrainer``, the program's normal entry
point for compiled training and the one ``families/gpt_train.py`` uses: the
model is ``paddle_tpu.models.GPT`` with the block's architecture fields at
OLMoE's values (``GPTConfig.olmoe_1b_7b()``), its sizes from the
configuration file under the keys of HF's ``config.json``. Every other knob of the trainer stays at the program's
default. A program without those fields (the parent of the PR that brought
them) fails in ``build`` at once, before any weight is made.
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import loader

def model_config(c: dict):
    from paddle_tpu.models import GPTConfig

    if c["num_key_value_heads"] != c["num_attention_heads"] \
            or c["norm_topk_prob"] or c["hidden_act"] != "silu" \
            or c["attention_bias"]:
        raise ValueError("the family runs full multi-head attention, "
                         "unrenormalised routing weights, SiLU and no bias")
    preset = GPTConfig.olmoe_1b_7b()
    return GPTConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        max_seq_len=c["max_position_embeddings"],
        ffn_hidden_size=c["ffn_hidden_size"],
        layer_norm_eps=c["rms_norm_eps"],
        tie_word_embeddings=c["tie_word_embeddings"],
        norm=preset.norm, position=preset.position,
        rope_theta=float(c["rope_theta"]), qk_norm=preset.qk_norm,
        bias=c["attention_bias"], ffn=preset.ffn,
        moe_num_experts=c["num_experts"],
        moe_top_k=c["num_experts_per_tok"],
        moe_expert_width=c["intermediate_size"], moe_dropless=True,
        moe_aux_weight=c["router_aux_loss_coef"],
        moe_z_weight=c["router_z_loss_coef"])


def build(ctx, n_micro: int):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh
    from paddle_tpu.models import GPT

    t = ctx.config["trainer"]
    cfg = model_config(ctx.config)
    paddle.seed(ctx.seed31)
    model = GPT(cfg)
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
                         "max_seq_len":
                         ctx.config["max_position_embeddings"]})
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
    # what the window's last step routed, outputs of that step: the
    # fullest expert's rows over the mean expert's, averaged over the
    # step's expert-layer calls
    routed = jax.device_get(tr.aux_stats)
    stats = [d.memory_stats() or {} for d in ctx.devices]
    live = max(int(s.get("bytes_in_use", 0)) for s in stats)
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
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
             "live_bytes": live}
    facts["moe_expert_load_max_over_mean"] = float(
        routed["moe/load_max"] * ctx.config["num_experts"]
        / routed["moe/assigned"])
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
