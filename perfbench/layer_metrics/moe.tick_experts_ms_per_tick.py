"""Device time the tick spends on the held experts: the gather of their rows,
the three grouped products (the Pallas calls ``moe_gmm``) and the
scatter-add back (``moe/dispatch``, ``moe/experts``, ``moe/combine``), all
expert layers, in the dots3 cell, in DeepSeek-V2's (four expert layers) and
in Ling-3.0-flash's (128 experts of 768 a layer at about one row each, six
expert layers)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "experts")
