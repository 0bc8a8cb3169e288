"""Device time a serving tick spends in operations under none of the program's
scope names: what XLA adds around the blocks (the scan's slicing and
stacking of the page pools, the copies around the ``cond``), mean over the
traced runs of the tick program."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.read_tick_part(run, "unscoped")
