"""Device time a served model's tick spends in its dense arithmetic, mean over
the traced runs of the tick program, as the cell's trace helper splits it.
dots3 (``_dots3_trace``) and DeepSeek-V2 (``_dsv2_trace``): ``blk/qkv``,
``blk/attn_out`` and what of ``blk/ffn`` is outside the ``moe/`` parts (norms,
RoPE, the latent and indexer projections, the absorbed queries, gates, the
output projection, the leading dense FFN). Olmo-Hybrid (``_olmoh_trace``):
every matrix product and norm of both kinds of layer (``blk/gdn/proj``,
``blk/gdn/out``, ``blk/qkv``, ``blk/kv_scatter``, ``blk/attn_out``,
``blk/ffn``). Ling-3.0-flash (``_ling3_trace``): ``blk/kda/proj``,
``blk/kda/out``, ``blk/qkv``, ``blk/attn_out`` and what of ``blk/ffn`` is
outside the ``moe/`` parts. Falcon-H1 (``_falcon_h1_trace``):
``blk/ssd/proj``, ``blk/ssd/out``, ``blk/qkv``, ``blk/kv_scatter``,
``blk/attn_out``, ``blk/ffn`` (norms, both mixers' projections and ways out,
the gated norm, RoPE, the K/V write and the SwiGLU). In every cell: the read
of the weights."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "dense")
