"""``moe.tick_route_ms_per_tick`` in DeepSeek-V2's cell: device time of the
softmax router under its group limit, the counting sort of the held rows and
the tick's routing statistics (``moe/route``, four expert layers)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dsv2_trace").read_part(
        run, "route")
