"""Device time the tick spends in the sliding layers' absorbed attention over
the window's pages (``blk/attn/swa``)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dots3_trace").read_part(
        run, "swa")
