"""Milliseconds the device lost to the holds the engine named inside the
judged window, in an open-loop cell, where a hold shows as a late token and
not as a lower rate (``served.hold_lost_ms_in_window``'s reading)."""
from perfbench import loader


def read(run):
    holds = loader.load_module("layer_metrics", "_holds")
    return holds.lost_ms(run)
