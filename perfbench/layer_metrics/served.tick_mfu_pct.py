"""Model FLOP/s utilization of a served model's whole tick: 2 operations a
parameter multiplied a token (dense matrices, the held experts' rows, the
head for the sampled rows) and the attention's or the delta rule's own
operations, by the family's own yardstick (its trace helper's
``tick_needs``: ``yardstick_mla.tick_flops`` for dots3's three attention parts,
``yardstick_mla_dense.tick_flops`` for DeepSeek-V2's dense attention in its
lesser form, ``yardstick_gdn.tick_flops`` for Olmo-Hybrid's delta rule in
both forms and its full layers' visible pairs, ``yardstick_ling3.tick_flops``
for Ling-3.0-flash's per-channel delta rule in both forms and its latent
attention's lesser form, ``yardstick_ssd.tick_flops`` for Falcon-H1's
state-space rule in both forms and its attention's visible pairs), over the tick's median device time and the chip's
published bf16 peak. A tick of some tens of rows is bound by HBM: this reads
low."""
from perfbench import loader


def read(run):
    needs = loader.load_module("layer_metrics", "_served").tick_needs(run)
    if needs is None:
        return None
    s, _, ops = needs
    return 100.0 * ops / (s["ms"] * 1e-3) / s["peak"].bf16_flops
