"""Laguna's whole tick against the time to move, once, what it must move by
``yardstick_laguna.tick_bytes`` (dense weights, the touched experts, the head,
both kinds of attention's K and V): ``served.tick_hbm_roofline_pct``'s
reader."""
from perfbench import loader


def read(run):
    needs = loader.load_module("layer_metrics", "_laguna_trace").needs(run)
    if needs is None:
        return None
    s, moved, _ = needs
    return 100.0 * moved / s["peak"].hbm_bytes_per_s * 1e3 / s["ms"]
