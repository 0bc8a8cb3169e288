"""``moe.tick_shared_ms_per_tick`` in DeepSeek-V2's cell: device time of the two
shared experts, one SwiGLU of 3,072 every token passes (``moe/shared``, four
expert layers)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dsv2_trace").read_part(
        run, "shared")
