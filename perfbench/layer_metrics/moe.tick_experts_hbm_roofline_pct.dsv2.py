"""``moe.tick_experts_hbm_roofline_pct`` in DeepSeek-V2's cell: the time to
read, once, the matrices of the held experts that were given a row (the
ticks' own count, ``experts_touched_share``), over
``moe.tick_experts_ms_per_tick.dsv2``. With about 20 rows an expert the
products are bound by the weights' bytes, not by arithmetic."""
from perfbench import loader, yardstick_mla_dense


def read(run):
    tr = loader.load_module("layer_metrics", "_dsv2_trace")
    s, ms = tr.tick_shape(run), tr.read_part(run, "experts")
    if s is None or not ms:
        return None
    least = yardstick_mla_dense.experts_bytes(
        run["ctx"].config, s["touched"]) / s["peak"].hbm_bytes_per_s * 1e3
    return 100.0 * least / ms
