"""Device time a training step spends in the experts: the three grouped
matmuls and the SiLU gate between them (operations under ``moe/experts``),
forward, recomputed forward and backward; mean over chips and traced
steps."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_moe_trace").read_part(
        run, "experts")
