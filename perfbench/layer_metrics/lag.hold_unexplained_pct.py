"""Share of the lost milliseconds of Laguna's window that no named cause
explains (``served.hold_unexplained_pct``'s reader)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_holds").unexplained_pct(run)
