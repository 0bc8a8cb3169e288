"""The decode step's share of its memory roofline: the live rows' states read
and written once and their operands (``yardstick_gdn.step_bytes``, unpadded:
2 x 2.21 MB a row and linear layer) at the chip's HBM peak, over the device
time of ``blk/gdn/step``. It reads the same work whatever implements it."""
from perfbench import loader, yardstick_gdn as y


def read(run):
    return loader.load_module("layer_metrics", "_olmoh_trace").roofline_pct(
        run, "gdn_step", lambda c, s, peak: y.least_ms(
            y.step_flops(c, s["live"]), y.step_bytes(c, s["live"]), peak))
