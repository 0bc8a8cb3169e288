"""``moe.tick_experts_ms_per_tick`` in DeepSeek-V2's cell: device time of the
held experts' grouped matmuls with their gather and scatter-add
(``moe/dispatch``, ``moe/experts``, ``moe/combine`` and the ``moe_gmm``
kernels, four expert layers)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dsv2_trace").read_part(
        run, "experts")
