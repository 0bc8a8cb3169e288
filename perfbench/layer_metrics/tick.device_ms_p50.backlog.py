"""Median device time of one serving tick under a backlog."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_tick").device_ms_p50(run)
