"""Median interval between the arrivals of consecutive waited-for ticks over
the whole judged window, from the engine's tick log: the device's tick seen
without a trace, to lay beside ``served.tick_device_ms_p50``
(``tick.device_ms_p50.backlog`` in the long-prompt cell) from the seconds
after the window. Every backlog cell's (Falcon-H1's ``fh1.`` copy until
PR 56)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_holds").tick_ms_p50(run)
