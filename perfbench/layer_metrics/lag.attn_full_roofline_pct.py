"""Laguna's full attention's share of its roofline: 8 key/value heads' K and V
of the rows' live keys read once and 4 x 128 operations a visible pair and
query head (48), the slower of the two by ``yardstick_laguna``, over the
part's device time (``attn.full_roofline_pct``'s reader)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_laguna_trace").roofline_pct(
        run, "attn")
