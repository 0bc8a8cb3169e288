"""Device time DeepSeek-V2's tick spends in its dense matrices: the attention's
projections (``blk/qkv``, ``blk/attn_out``) and what of ``blk/ffn`` is not the
router, the held experts or the shared experts (the leading dense FFN, the
norms)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dsv2_trace").read_part(
        run, "dense")
