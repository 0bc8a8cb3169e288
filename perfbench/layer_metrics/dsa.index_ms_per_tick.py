"""Device time the tick spends scoring every query against its row's live
indexer keys (``blk/index``), both full layers."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dots3_trace").read_part(
        run, "index")
