"""Model FLOP/s utilization of a sparse model's training step: tokens a
second times the operations one token's forward and backward passes need,
over the chips' published peak. Recomputed operations are not counted. One
entry for the two sparse training cells since PR 48 (``solar2.train_mfu_pct``
was Solar-Open2's copy), the operations by the family's own yardstick:
OLMoE's on active parameters (``6 N_active + 12 L h s``,
``yardstick_moe.olmoe_train_flops_per_token``); the Solar-Open2 cut's, whose
configuration states a ``linear_attn_config``, 6 a parameter it multiplies
with on this chip, Megatron's term for the softmax layer and the scans' own
operations (``yardstick_kda.train_flops_per_token``)."""
from perfbench import yardstick, yardstick_kda, yardstick_moe


def read(run):
    f, ctx = run["facts"], run["ctx"]
    if "tokens_per_s" not in f or "num_experts_per_tok" not in ctx.config:
        return None
    peak = yardstick.chip_peak(ctx.devices[0].device_kind).bf16_flops
    if "linear_attn_config" in ctx.config:
        flops = yardstick_kda.train_flops_per_token(ctx.config, f["seq"])
    else:
        flops = yardstick_moe.olmoe_train_flops_per_token(ctx.config,
                                                          f["seq"])
    return 100.0 * f["tokens_per_s"] * flops / (len(ctx.devices) * peak)
