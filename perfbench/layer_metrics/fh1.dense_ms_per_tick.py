"""Device time Falcon-H1's tick spends in its dense arithmetic, mean over the
traced runs of the tick program (``_falcon_h1_trace``: ``blk/ssd/proj``,
``blk/ssd/out``, ``blk/qkv``, ``blk/kv_scatter``, ``blk/attn_out``,
``blk/ffn``: norms, both mixers' projections and ways out, the gated norm,
RoPE, the K/V write and the SwiGLU): the read of the layers' weights."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_falcon_h1_trace").read_part(
        run, "dense")
