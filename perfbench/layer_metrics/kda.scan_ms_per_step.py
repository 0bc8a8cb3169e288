"""Device time a training step spends in the gated delta-rule scan: the
Pallas calls named ``kda_fwd`` (the forward pass and its recomputation) and
``kda_bwd_*`` (the chunk states' sweep and the backward sweep) of every
linear-attention layer and micro-batch; mean over chips and traced steps."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_kda_trace").scan_ms(run)
