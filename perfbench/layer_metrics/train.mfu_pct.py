"""Model FLOP/s utilization: tokens a second times the operations the
forward and backward passes need for a token, over the chips' published
peak. Recomputed operations are not counted."""
from perfbench import yardstick


def read(run):
    f, ctx = run["facts"], run["ctx"]
    if "tokens_per_s" not in f:
        return None
    peak = yardstick.chip_peak(ctx.devices[0].device_kind).bf16_flops
    flops = yardstick.gpt_train_flops_per_token(ctx.config, f["seq"])
    return 100.0 * f["tokens_per_s"] * flops / (len(ctx.devices) * peak)
