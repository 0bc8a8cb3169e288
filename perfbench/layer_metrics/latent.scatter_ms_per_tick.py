"""Device time the tick spends writing its tokens' rows into the pages they
touch (``blk/latent_scatter``), all layers: dots3's latent, indexer-key and
windowed pools; DeepSeek-V2's latent pool (five layers); Ling-3.0-flash's
(its one MLA layer)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "scatter")
