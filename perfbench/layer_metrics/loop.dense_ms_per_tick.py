"""Device time a looped model's serving tick spends in the blocks' dense arithmetic
(scopes ``blk/qkv``, ``blk/attn_out``, ``blk/ffn``: the norms, RoPE, the seven
projections, SiLU, residuals), all loop steps together, mean over the traced
runs of the tick program."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_loop_trace").read_part(
        run, "dense")
