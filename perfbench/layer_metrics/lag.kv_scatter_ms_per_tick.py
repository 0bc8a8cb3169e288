"""Device time Laguna's tick spends writing its tokens' keys and values into
the six layers' grouped pages, a page at a time (``blk/kv_scatter``)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_laguna_trace").read_part(
        run, "scatter")
