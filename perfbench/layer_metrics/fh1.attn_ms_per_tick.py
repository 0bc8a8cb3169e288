"""Device time of grouped-query attention over the K/V pages
(``blk/attn/full``: ``grouped_paged_attn`` for the 80 decode rows and for the
chunk row's pieces, nine layers), mean over the traced runs of the tick
program."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_falcon_h1_trace").read_part(
        run, "attn")
