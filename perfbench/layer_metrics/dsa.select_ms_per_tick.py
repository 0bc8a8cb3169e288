"""Device time the tick spends choosing each query's ``index_topk`` best of its
scores (``blk/select``), both full layers."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dots3_trace").read_part(
        run, "select")
