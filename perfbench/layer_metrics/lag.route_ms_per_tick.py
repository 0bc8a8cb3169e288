"""Device time Laguna's tick spends routing (``moe/route``), five expert
layers: scores over 256 experts by sigmoid, ten rounds of argmax, the
counting sort of the held rows and the tick's routing statistics
(``moe.tick_route_ms_per_tick``'s reader)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_laguna_trace").read_part(
        run, "route")
