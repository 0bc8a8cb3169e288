"""The chunked delta rule's share of its roofline: the chunk rows' products in
the chunked form at unpadded widths and their operands and states moved once
(``yardstick_gdn.chunk_flops``, ``chunk_bytes``), the slower of the two, over
the device time of ``blk/gdn/chunk``."""
from perfbench import loader, yardstick_gdn as y


def read(run):
    return loader.load_module("layer_metrics", "_olmoh_trace").roofline_pct(
        run, "gdn_chunk", lambda c, s, peak: y.least_ms(
            y.chunk_flops(c, s["chunk"]),
            y.chunk_bytes(c, s["chunk"], s["chunk_rows"]), peak))
