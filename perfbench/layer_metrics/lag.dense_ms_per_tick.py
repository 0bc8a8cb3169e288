"""Device time Laguna's tick spends in its dense arithmetic (``blk/qkv`` with
the gate's projection, ``blk/attn_out`` with the gate's multiply, the dense
SwiGLU and what of ``blk/ffn`` is outside the ``moe/`` parts), mean over the
traced ticks: ``served.dense_ms_per_tick``'s reader."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_laguna_trace").read_part(
        run, "dense")
