"""Device time a training step spends in the shared expert (operations under
``moe/shared``: one SwiGLU every token passes), forward, recomputed forward
and backward; mean over chips and traced steps."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_kda_trace").read_part(
        run, "shared")
