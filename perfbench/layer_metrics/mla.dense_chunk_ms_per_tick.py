"""Device time the tick spends in the dense latent attention of its chunk rows
(``blk/attn/mla_chunk``: two rows of 256 queries over everything behind them,
five layers)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dsv2_trace").read_part(
        run, "mla_chunk")
