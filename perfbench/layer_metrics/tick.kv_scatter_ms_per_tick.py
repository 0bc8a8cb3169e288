"""Device time a serving tick spends in writing the tick's new keys and values
into the layers' pages (scope ``blk/kv_scatter``: both ``paged_kv_scatter``
calls), mean over the traced runs of the tick program."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.read_tick_part(run, "kv_scatter")
