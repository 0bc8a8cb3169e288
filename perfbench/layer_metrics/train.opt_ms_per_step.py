"""Device time a training step spends in the optimizer: gradient clip and the
AdamW update (scope ``opt/update``); mean over chips and traced steps."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.read_step_part(run, "opt")
