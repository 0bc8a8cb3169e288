"""Model FLOP/s utilization of a looped model's tick: 2 operations a
parameter multiplied a token (the blocks once a loop step, the head for the
sampled rows) and the attention over the live positions
(``yardstick_loop.tick_flops``), over the tick's median device time and the
chip's published bf16 peak. Small by nature: a tick multiplies every weight
with some ten tokens."""
from perfbench import loader, yardstick_loop


def read(run):
    s = loader.load_module("layer_metrics", "_loop_trace").tick_shape(run)
    if s is None:
        return None
    return yardstick_loop.mfu_pct(
        s["ms"], run["ctx"].config, s["live"], s["tokens"], s["sampled"],
        s["peak"].bf16_flops)
