"""Model FLOP/s utilization of the latent-attention model's tick: 2 operations
a parameter multiplied a token (dense matrices, the held experts' rows, the
head for the sampled rows) and the three attention parts
(``yardstick_mla.tick_flops``), over the tick's median device time and the
chip's published bf16 peak."""
from perfbench import loader, yardstick_mla


def read(run):
    s = loader.load_module("layer_metrics", "_dots3_trace").tick_shape(run)
    if s is None:
        return None
    ops = yardstick_mla.tick_flops(
        run["ctx"].config, s["decode"], s["chunks"], s["chunk"],
        s["context"], s["sampled"], s["expert_rows"])
    return 100.0 * ops / (s["ms"] * 1e-3) / s["peak"].bf16_flops
