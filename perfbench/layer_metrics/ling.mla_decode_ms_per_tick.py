"""Device time the tick spends in the dense latent attention of its decode rows
(``blk/mla/decode``: a query a live slot over its whole context, the one MLA
layer, 32 heads absorbed over rows of 576)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_ling3_trace").read_part(
        run, "mla_decode")
