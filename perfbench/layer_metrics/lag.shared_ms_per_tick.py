"""Device time Laguna's tick spends in the shared expert every token passes
(``moe/shared``; ``moe.tick_shared_ms_per_tick``'s reader)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_laguna_trace").read_part(
        run, "shared")
