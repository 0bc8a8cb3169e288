"""The state-space decode step's share of its memory roofline: the live rows'
states read and written once and their operands (``yardstick_ssd.step_bytes``,
unpadded: 2 x 4.19 MB a row and layer) at the chip's HBM peak, over the
device time of ``blk/ssd/step``. It reads the same work whatever implements
it."""
from perfbench import loader, yardstick_ssd as y


def read(run):
    return loader.load_module("layer_metrics", "_falcon_h1_trace").roofline_pct(
        run, "ssd_step", lambda c, s, peak: y.least_ms(
            y.step_flops(c, s["live"]), y.step_bytes(c, s["live"]), peak))
