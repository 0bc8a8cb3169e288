"""Device time the tick spends in the dense latent attention of its decode rows
(``blk/attn/mla_decode``: a query a live slot over its whole context, five
layers)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dsv2_trace").read_part(
        run, "mla_decode")
