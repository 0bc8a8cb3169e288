"""Device time a training step spends in the embedding and the lm-head with its
cross-entropy, forward and backward (scopes ``fwd/stem`` and ``fwd/head``
outside any block); mean over chips and traced steps."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.read_step_part(run, "head")
