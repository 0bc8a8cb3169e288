"""The windowed absorbed attention's share of its roofline: the least time for
the window's latents a query, read once a row (``yardstick_mla.swa_ops_bytes``),
over ``swa.attn_ms_per_tick``."""
from perfbench import loader, yardstick_mla


def read(run):
    return loader.load_module("layer_metrics", "_dots3_trace").roofline_pct(
        run, "swa", yardstick_mla.swa_ops_bytes)
