"""Device time the tick spends routing (``moe/route``: the router's product,
the rounds of argmax, the counting sort of the held assignments), all expert
layers."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dots3_trace").read_part(
        run, "route")
