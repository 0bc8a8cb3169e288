"""``latent.scatter_ms_per_tick`` in DeepSeek-V2's cell: device time the tick
spends writing its tokens' latent rows into the pages they touch
(``blk/latent_scatter``, five layers)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dsv2_trace").read_part(
        run, "scatter")
