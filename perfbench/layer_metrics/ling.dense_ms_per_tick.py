"""Device time Ling-3.0-flash's tick spends in its dense arithmetic, mean over
the traced runs of the tick program (``_ling3_trace``: ``blk/kda/proj``,
``blk/kda/out``, ``blk/qkv``, ``blk/attn_out`` and what of ``blk/ffn`` is
outside the ``moe/`` parts: norms, the mixers' projections, gates and ways
out, RoPE, the absorbed queries, the leading dense FFN): the read of the
weights."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_ling3_trace").read_part(
        run, "dense")
