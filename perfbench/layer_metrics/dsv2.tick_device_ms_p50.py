"""Median device time of one run of DeepSeek-V2's tick program (``tick.device_ms_p50.*``'s
reading, in the cell whose tick it is)."""
from perfbench import loader


def read(run):
    if loader.load_module("layer_metrics", "_dsv2_trace").parts_ms(run) \
            is None:
        return None
    return loader.load_module("layer_metrics", "_tick").device_ms_p50(run)
