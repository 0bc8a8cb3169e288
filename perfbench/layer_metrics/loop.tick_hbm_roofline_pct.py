"""A looped model's tick against the chip's memory bandwidth: the least time
to read what a tick must (the block weights once a loop step, the head, the
live positions' keys and values over all 192 cache layers;
``yardstick_loop.tick_bytes``, from the configuration alone) at the chip's
published bytes a second, over the tick's median device time."""
from perfbench import loader, yardstick_loop


def read(run):
    s = loader.load_module("layer_metrics", "_loop_trace").tick_shape(run)
    if s is None:
        return None
    return yardstick_loop.hbm_roofline_pct(
        s["ms"], run["ctx"].config, s["live"], s["tokens"],
        s["peak"].hbm_bytes_per_s)
