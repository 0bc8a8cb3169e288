"""The experts' share of their roofline: the least time the chip could take
for the three expert products of every layer and micro-batch of a step,
forward and backward (``yardstick_moe.expert_ops_bytes``; recomputation is
not counted, so a step that recomputes the forward pass cannot pass 75 %),
over ``moe.experts_ms_per_step``."""
from perfbench import loader, yardstick, yardstick_moe


def read(run):
    ms = loader.load_module("layer_metrics", "_moe_trace").read_part(
        run, "experts")
    if not ms:
        return None
    f, ctx = run["facts"], run["ctx"]
    peak = yardstick.chip_peak(ctx.devices[0].device_kind)
    return yardstick_moe.experts_roofline_pct(
        ms, f["micro"] * f["seq"], f["n_micro"], ctx.config, peak)
