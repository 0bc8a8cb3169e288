"""Median device time of one run of Olmo-Hybrid's tick program
(``tick.device_ms_p50.*``'s reading, in the cell whose tick it is)."""
from perfbench import loader


def read(run):
    if loader.load_module("layer_metrics", "_olmoh_trace").parts_ms(run) \
            is None:
        return None
    return loader.load_module("layer_metrics", "_tick").device_ms_p50(run)
