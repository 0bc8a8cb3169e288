"""Model FLOP/s utilization of Olmo-Hybrid's whole tick: 2 operations a
parameter multiplied a token, the head for the sampled rows, the delta rule's
two forms and the full layers' visible pairs (``yardstick_gdn.tick_flops``),
over the tick's median device time and the chip's published bf16 peak."""
from perfbench import loader, yardstick_gdn


def read(run):
    s = loader.load_module("layer_metrics", "_olmoh_trace").tick_shape(run)
    if s is None:
        return None
    ops = yardstick_gdn.tick_flops(run["ctx"].config, s)
    return 100.0 * ops / (s["ms"] * 1e-3) / s["peak"].bf16_flops
