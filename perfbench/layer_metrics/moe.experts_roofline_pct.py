"""The experts' share of their roofline: the least time the chip could take
for the three expert products of every layer and micro-batch of a step,
forward and backward (recomputation is not counted, so a step that
recomputes the forward pass cannot pass 75 %), over
``moe.experts_ms_per_step``. One entry for the two sparse training cells
since PR 48 (``moe.held_experts_roofline_pct`` was Solar-Open2's copy). In
the cell that holds every expert (OLMoE) the rows are every token's
(``yardstick_moe.expert_ops_bytes``). Where the family counts the rows its
held experts were given (``moe_rows_held``: the step's own count, summed
over layers and micro-batches; Solar-Open2 holds 8 of 320), the rows are
those and the matrices the held experts'
(``yardstick_kda.held_experts_ops_bytes``): groups of about 200 rows keep
that cell far from the grouped matmul's own share at deployment load."""
from perfbench import loader, yardstick, yardstick_kda, yardstick_moe


def read(run):
    ms = loader.load_module("layer_metrics", "_moe_trace").read_part(
        run, "experts")
    if not ms:
        return None
    f, ctx = run["facts"], run["ctx"]
    peak = yardstick.chip_peak(ctx.devices[0].device_kind)
    if "moe_rows_held" in f:
        if not f["moe_rows_held"]:
            return None
        return yardstick_kda.held_experts_roofline_pct(
            ms, f["moe_rows_held"], f["n_micro"], ctx.config, peak)
    return yardstick_moe.experts_roofline_pct(
        ms, f["micro"] * f["seq"], f["n_micro"], ctx.config, peak)
