"""Device time the tick spends in the dense latent attention of its decode rows,
a query a live slot over its whole context, as the cell's trace helper cuts
it (part ``mla_decode``): DeepSeek-V2's ``blk/attn/mla_decode`` (five layers),
Ling-3.0-flash's ``blk/mla/decode`` (its one MLA layer, 32 heads absorbed
over rows of 576)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "mla_decode")
