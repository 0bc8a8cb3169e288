"""Device time a training step spends after the scan in the linear-attention
layers (operations under ``blk/kda/out``: the heads' norm, the gate, the
output projection and the residual), forward, recomputed forward and
backward; mean over chips and traced steps."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_kda_trace").read_part(
        run, "out")
