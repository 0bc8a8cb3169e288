"""Model FLOP/s utilization on active parameters: tokens a second times
the operations one token's forward and backward passes need (``6 N_active +
12 L h s``, ``yardstick_moe.olmoe_train_flops_per_token``), over the chips'
published peak. Recomputed operations are not counted."""
from perfbench import yardstick, yardstick_moe


def read(run):
    f, ctx = run["facts"], run["ctx"]
    if "tokens_per_s" not in f or "num_experts_per_tok" not in ctx.config:
        return None
    peak = yardstick.chip_peak(ctx.devices[0].device_kind).bf16_flops
    flops = yardstick_moe.olmoe_train_flops_per_token(ctx.config, f["seq"])
    return 100.0 * f["tokens_per_s"] * flops / (len(ctx.devices) * peak)
