"""Device time of the held experts (``moe/dispatch``, ``moe/experts``,
``moe/combine`` and the grouped matmuls by their instruction's name): 128
experts of 768 a layer at about one row each, six expert layers."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_ling3_trace").read_part(
        run, "experts")
