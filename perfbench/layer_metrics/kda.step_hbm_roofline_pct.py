"""The per-channel decode step's share of its memory roofline: the live rows'
states read and written once and their operands (``yardstick_ling3.
step_bytes``, unpadded: 2 x 2.10 MB a row and KDA layer) at the chip's HBM
peak, over the device time of ``blk/kda/step``. It reads the same work
whatever implements it."""
from perfbench import loader, yardstick_ling3 as y


def read(run):
    return loader.load_module("layer_metrics", "_ling3_trace").roofline_pct(
        run, "kda_step", lambda c, s, peak: y.least_ms(
            y.step_flops(c, s["live"]), y.step_bytes(c, s["live"]), peak))
