"""Device time a latent-attention model's serving tick spends in the blocks'
dense arithmetic (scopes ``blk/qkv``, ``blk/attn_out``, ``blk/ffn`` outside the
``moe/`` parts: norms, RoPE, the latent and indexer projections, the absorbed
queries, gates, the output projection, the leading dense FFN), mean over the
traced runs of the tick program."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dots3_trace").read_part(
        run, "dense")
