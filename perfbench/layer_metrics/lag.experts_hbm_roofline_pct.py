"""Laguna's held experts' share of their memory roofline: the time to read,
once, the matrices of the held experts that were given a row
(``yardstick_laguna.experts_bytes``) over ``lag.experts_ms_per_tick``
(``moe.tick_experts_hbm_roofline_pct``'s reader)."""
from perfbench import loader


def read(run):
    needs = loader.load_module(
        "layer_metrics", "_laguna_trace").experts_needs(run)
    if needs is None:
        return None
    s, moved, ms = needs
    least = moved / s["peak"].hbm_bytes_per_s * 1e3
    return 100.0 * least / ms
