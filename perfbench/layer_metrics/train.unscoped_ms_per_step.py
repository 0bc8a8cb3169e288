"""Device time a training step spends in operations under none of those names:
the layer scan's stacking of saved activations, the pipeline's sends and its
collectives outside a block, gradient accumulation; mean over chips and
traced steps."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.read_step_part(run, "unscoped")
