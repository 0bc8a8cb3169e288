"""Median interval between the arrivals of consecutive waited-for ticks over
the whole judged window, from the engine's tick log (``_holds.tick_ms_p50``):
the device's tick seen without a trace, to lay beside
``fh1.tick_device_ms_p50`` from the seconds after the window."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_holds").tick_ms_p50(run)
