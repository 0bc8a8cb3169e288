"""Device time Olmo-Hybrid's tick spends in the matrix products and norms of
both kinds of layer: a linear layer's projections and its gated way out
(``blk/gdn/proj``, ``blk/gdn/out``), a full layer's projections and K/V writes
(``blk/qkv``, ``blk/kv_scatter``, ``blk/attn_out``) and every block's SwiGLU
and norms (``blk/ffn``): the read of the weights."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_olmoh_trace").read_part(
        run, "dense")
