"""Milliseconds the device lost to the holds the engine named inside the
judged window (``_holds.lost_ms``: the sum of the ``hold`` events'
``lost_ms`` from the engine's tick record). 0.0 for a clean window."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_holds").lost_ms(run)
