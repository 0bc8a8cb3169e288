"""Laguna's windowed attention's share of its roofline: K and V of at most
``sliding_window`` keys a query read once a row and 4 x 128 operations a
visible pair and query head (72), the slower of the two by
``yardstick_laguna``, over the part's device time."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_laguna_trace").roofline_pct(
        run, "attn_window")
