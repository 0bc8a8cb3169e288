"""The scan's share of its roofline: the least time the chip could take for
a step's scans, forward and backward of every linear-attention layer and
sequence (``yardstick_kda.kda_ops_bytes``; recomputation is not counted),
over ``kda.scan_ms_per_step``."""
from perfbench import loader, yardstick, yardstick_kda


def read(run):
    ms = loader.load_module("layer_metrics", "_kda_trace").scan_ms(run)
    if not ms:
        return None
    f, ctx = run["facts"], run["ctx"]
    peak = yardstick.chip_peak(ctx.devices[0].device_kind)
    return yardstick_kda.scan_roofline_pct(
        ms, f["seq"], f["micro"] * f["n_micro"], ctx.config, peak)
