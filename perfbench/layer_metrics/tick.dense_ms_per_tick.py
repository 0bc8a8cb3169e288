"""Device time a serving tick spends in the block's dense arithmetic (scopes
``blk/qkv``, ``blk/attn_out``, ``blk/ffn``: layer norms, the four matmuls,
GELU, residuals), mean over the traced runs of the tick program."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.read_tick_part(run, "dense")
