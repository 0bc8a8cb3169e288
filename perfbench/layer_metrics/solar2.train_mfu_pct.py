"""Model FLOP/s utilization of the Solar-Open2 cut: tokens a second times
the operations one token's forward and backward passes need here (6 a
parameter it multiplies with on this chip, Megatron's term for the softmax
layer and the scans' own operations,
``yardstick_kda.train_flops_per_token``), over the chips' published peak.
Recomputed operations are not counted."""
from perfbench import yardstick, yardstick_kda


def read(run):
    f, ctx = run["facts"], run["ctx"]
    if "tokens_per_s" not in f or "linear_attn_config" not in ctx.config:
        return None
    peak = yardstick.chip_peak(ctx.devices[0].device_kind).bf16_flops
    flops = yardstick_kda.train_flops_per_token(ctx.config, f["seq"])
    return 100.0 * f["tokens_per_s"] * flops / (len(ctx.devices) * peak)
