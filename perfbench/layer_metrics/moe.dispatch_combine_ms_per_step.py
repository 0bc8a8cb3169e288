"""Device time a training step spends moving rows to and from the experts:
the gather of each assignment's row and the gate-weighted un-sort and sum
(operations under ``moe/dispatch`` and ``moe/combine``), forward, recomputed
forward and backward; mean over chips and traced steps."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_moe_trace").read_part(
        run, "dispatch_combine")
