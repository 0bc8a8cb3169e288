"""Device time a training step spends in the blocks' dense arithmetic, forward,
recomputed forward and backward (operations under a ``blk/`` scope, less the
Pallas flash calls); mean over chips and traced steps."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.read_step_part(run, "dense")
