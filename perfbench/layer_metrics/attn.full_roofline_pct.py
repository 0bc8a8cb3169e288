"""The full layers' attention's share of its roofline: 30 heads' K and V of the
rows' live keys read once and 4 x 128 operations a visible pair and head
(``yardstick_gdn.attention_bytes``, ``attention_flops``), the slower of the
two, over the device time of ``blk/attn``. The pools' two head rows of zeros
are not counted: they are the implementation's."""
from perfbench import loader, yardstick_gdn as y


def read(run):
    return loader.load_module("layer_metrics", "_olmoh_trace").roofline_pct(
        run, "attn", lambda c, s, peak: y.least_ms(
            y.attention_flops(c, s["decode_keys"] + s["chunk_pairs"]),
            y.attention_bytes(c, s["decode_keys"] + s["chunk_keys"]), peak))
