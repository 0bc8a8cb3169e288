"""Grouped-query attention's share of its roofline: 4 heads' K and V of the
rows' live keys read once and 4 x 128 operations a visible pair and query
head (``yardstick_ssd.attention_bytes``, ``attention_flops``), the slower of
the two, over the device time of ``blk/attn/full``. 80 rows of ~660 keys at
2,048 B a key and layer are little to move: the walk over pages of 16 binds,
and this reads low."""
from perfbench import loader, yardstick_ssd as y


def read(run):
    return loader.load_module("layer_metrics", "_falcon_h1_trace").roofline_pct(
        run, "attn", lambda c, s, peak: y.least_ms(
            y.attention_flops(c, s["decode_keys"] + s["chunk_pairs"]),
            y.attention_bytes(c, s["decode_keys"] + s["chunk_keys"]), peak))
