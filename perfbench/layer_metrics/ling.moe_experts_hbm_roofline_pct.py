"""The held experts' share of their memory roofline: the time to read, once,
the matrices of the held experts that were given a row (the ticks' own
count, ``experts_touched_share``; ``yardstick_ling3.experts_bytes``), over
``ling.moe_experts_ms_per_tick``. With about one row an expert the products
are bound by the weights' bytes."""
from perfbench import loader


def read(run):
    tr = loader.load_module("layer_metrics", "_ling3_trace")
    s, moved = tr.tick_shape(run), tr.experts_bytes(run)
    ms = tr.read_part(run, "experts")
    if s is None or not ms:
        return None
    return 100.0 * moved / s["peak"].hbm_bytes_per_s * 1e3 / ms
