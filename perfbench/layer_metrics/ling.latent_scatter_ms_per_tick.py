"""Device time the tick spends writing its tokens' latent rows into the pages
they touch (``blk/latent_scatter``, the one MLA layer)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_ling3_trace").read_part(
        run, "scatter")
