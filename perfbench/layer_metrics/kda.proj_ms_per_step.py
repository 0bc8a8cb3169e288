"""Device time a training step spends in the linear-attention layers'
projections (operations under ``blk/kda/proj``: the five projections, the
short convolutions, ``l2norm`` and the gates' arithmetic), forward,
recomputed forward and backward; mean over chips and traced steps."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_kda_trace").read_part(
        run, "proj")
