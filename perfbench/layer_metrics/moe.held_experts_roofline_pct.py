"""The held experts' share of their roofline: the least time the chip could
take for the three expert products of a step, forward and backward, over the
rows the held experts were given in it (the step's own count, summed over
layers and micro-batches: the family's ``moe_rows_held``) and the matrices
of the experts held (``yardstick_kda.held_experts_ops_bytes``;
recomputation is not counted), over ``moe.experts_ms_per_step``. Groups of
about 200 rows keep it far from the grouped matmul's own share at
deployment load, which ``moe.experts_roofline_pct`` reads in the cell that
holds every expert."""
from perfbench import loader, yardstick, yardstick_kda


def read(run):
    rows = run["facts"].get("moe_rows_held")
    ms = loader.load_module("layer_metrics", "_moe_trace").read_part(
        run, "experts")
    if not ms or not rows:
        return None
    f, ctx = run["facts"], run["ctx"]
    peak = yardstick.chip_peak(ctx.devices[0].device_kind)
    return yardstick_kda.held_experts_roofline_pct(
        ms, rows, f["n_micro"], ctx.config, peak)
