"""Median device time of one run of the served model's tick program
(``tick.device_ms_p50.*``'s reading, in the cells whose tick is a
latent-attention model's, DeepSeek-V2's, Olmo-Hybrid's, Ling-3.0-flash's or
Falcon-H1's: a run whose tick none of their trace helpers reads gives
nothing)."""
from perfbench import loader


def read(run):
    if loader.load_module("layer_metrics", "_served").trace_of(run) is None:
        return None
    return loader.load_module("layer_metrics", "_tick").device_ms_p50(run)
