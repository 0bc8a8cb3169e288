"""Device time the tick spends writing the tokens' rows into the latent, the
indexer-key and the windowed pools (``blk/latent_scatter``), all layers."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dots3_trace").read_part(
        run, "scatter")
