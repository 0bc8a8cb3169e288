"""Device time a training step spends routing: the router's matmul, the
softmax, the rounds of top-k and the ordering of the assignments by expert
(operations under ``moe/route``), forward, recomputed forward and backward;
mean over chips and traced steps."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_moe_trace").read_part(
        run, "route")
