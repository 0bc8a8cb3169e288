"""Of the excess milliseconds of all the window's holds, the share that the
thread's own counters do not cover, in an open-loop cell
(``served.hold_unexplained_pct``'s reading)."""
from perfbench import loader


def read(run):
    holds = loader.load_module("layer_metrics", "_holds")
    return holds.unexplained_pct(run)
