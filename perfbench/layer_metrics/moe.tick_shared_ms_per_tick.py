"""Device time the tick spends in the shared experts every token passes
(``moe/shared``), all expert layers: dots3's one shared expert,
DeepSeek-V2's two (one SwiGLU of 3,072, four expert layers), Ling-3.0-flash's
one (a SwiGLU of 768, six expert layers)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "shared")
