"""Model FLOP/s utilization of DeepSeek-V2's whole tick: 2 operations a
parameter multiplied a token (dense matrices, the held experts' rows, the
head for the sampled rows) and the dense attention's lesser form
(``yardstick_mla_dense.tick_flops``), over the tick's median device time and
the chip's published bf16 peak."""
from perfbench import loader, yardstick_mla_dense


def read(run):
    s = loader.load_module("layer_metrics", "_dsv2_trace").tick_shape(run)
    if s is None:
        return None
    ops = yardstick_mla_dense.tick_flops(
        run["ctx"].config, s["tokens"], (s["decode"], s["chunk"]),
        s["sampled"], s["expert_rows"])
    return 100.0 * ops / (s["ms"] * 1e-3) / s["peak"].bf16_flops
