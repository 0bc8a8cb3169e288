"""Milliseconds the device lost to the holds the engine named inside Laguna's
judged window (``served.hold_lost_ms_in_window``'s reader); 0.0 for a clean
window."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_holds").lost_ms(run)
