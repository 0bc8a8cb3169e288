"""Median device time of one run of Ling-3.0-flash's tick program
(``served.tick_device_ms_p50``'s reading, ``_tick.device_ms_p50``: a run whose
tick ``_ling3_trace`` does not read gives nothing)."""
from perfbench import loader


def read(run):
    if loader.load_module("layer_metrics", "_ling3_trace").parts_ms(run) \
            is None:
        return None
    return loader.load_module("layer_metrics", "_tick").device_ms_p50(run)
