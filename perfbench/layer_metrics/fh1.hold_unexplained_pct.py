"""Of the excess milliseconds of all the window's holds, the share that the
thread's own counters do not cover (``_holds.unexplained_pct``). 0.0 for a
window without a hold."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_holds").unexplained_pct(run)
