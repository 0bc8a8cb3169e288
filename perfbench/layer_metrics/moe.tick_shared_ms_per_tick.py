"""Device time the tick spends in the shared expert every token passes
(``moe/shared``), all expert layers."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dots3_trace").read_part(
        run, "shared")
