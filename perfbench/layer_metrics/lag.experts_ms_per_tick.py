"""Device time Laguna's tick spends in its 64 held experts a layer, dispatch
and combine with them (``moe.tick_experts_ms_per_tick``'s reader)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_laguna_trace").read_part(
        run, "experts")
