"""Model FLOP/s utilization of Laguna's whole tick: 2 operations a parameter
multiplied a token (dense matrices, the held experts' rows, the head for the
sampled rows) and both kinds of attention's visible pairs by
``yardstick_laguna.tick_flops``, over the tick's median device time and the
chip's published bf16 peak: ``served.tick_mfu_pct``'s reader, the cell's one
share of the whole tick's peak."""
from perfbench import loader


def read(run):
    needs = loader.load_module("layer_metrics", "_laguna_trace").needs(run)
    if needs is None:
        return None
    s, _, ops = needs
    return 100.0 * ops / (s["ms"] * 1e-3) / s["peak"].bf16_flops
