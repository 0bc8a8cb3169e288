"""Median interval between Laguna's ticks inside the judged window, from the
engine's own record (``served.tick_ms_p50_in_window``'s reader)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_holds").tick_ms_p50(run)
