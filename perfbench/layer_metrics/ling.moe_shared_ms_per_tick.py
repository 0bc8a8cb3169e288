"""Device time of the shared expert (``moe/shared``: one SwiGLU of 768 every
token passes), six expert layers."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_ling3_trace").read_part(
        run, "shared")
