"""The chunked scan's share of its roofline: the chunk rows' products in the
chunked form at blocks of ``mamba_chunk_size`` and their operands and states
moved once (``yardstick_ssd.chunk_flops``, ``chunk_bytes``), the slower of
the two, over the device time of ``blk/ssd/chunk``. The mean tick holds a
fifth of a chunk and the program runs the chunk row in every tick: this reads
low."""
from perfbench import loader, yardstick_ssd as y


def read(run):
    return loader.load_module("layer_metrics", "_falcon_h1_trace").roofline_pct(
        run, "ssd_chunk", lambda c, s, peak: y.least_ms(
            y.chunk_flops(c, s["chunk"]),
            y.chunk_bytes(c, s["chunk"], s["chunk_rows"]), peak))
