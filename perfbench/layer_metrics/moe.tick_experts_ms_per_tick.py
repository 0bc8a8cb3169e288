"""Device time the tick spends on the held experts: the gather of their rows,
the three grouped products (the Pallas calls ``moe_gmm``) and the
scatter-add back (``moe/dispatch``, ``moe/experts``, ``moe/combine``), all
expert layers, in the dots3 cell and in DeepSeek-V2's (four expert
layers)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "experts")
