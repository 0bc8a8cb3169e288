"""Model FLOP/s utilization of Ling-3.0-flash's whole tick: 2 operations a
parameter multiplied a token (dense matrices, the held experts' rows, the
head for the sampled rows), the delta rule's in both forms and the latent
attention's lesser form (``yardstick_ling3.tick_flops`` through
``_ling3_trace.needs``), over the tick's median device time and the chip's
published bf16 peak. A tick of 64 rows is bound by HBM: this reads low."""
from perfbench import loader


def read(run):
    needs = loader.load_module("layer_metrics", "_ling3_trace").needs(run)
    if needs is None:
        return None
    s, _, ops = needs
    return 100.0 * ops / (s["ms"] * 1e-3) / s["peak"].bf16_flops
