"""Model FLOP/s utilization of Falcon-H1's whole tick: 2 operations a parameter
multiplied a token, the head for the sampled rows, the state-space rule in
both forms and attention's visible pairs (``yardstick_ssd.tick_flops``
through ``_falcon_h1_trace.needs``), over the tick's median device time and
the chip's published bf16 peak. A tick of 80 rows is bound by HBM: this reads
low."""
from perfbench import loader


def read(run):
    needs = loader.load_module("layer_metrics", "_falcon_h1_trace").needs(run)
    if needs is None:
        return None
    s, _, ops = needs
    return 100.0 * ops / (s["ms"] * 1e-3) / s["peak"].bf16_flops
