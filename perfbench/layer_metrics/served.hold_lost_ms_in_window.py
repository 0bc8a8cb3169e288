"""Milliseconds the device lost to the holds the engine named inside the
judged window, in the backlog cells: the sum of the ``hold`` events'
``lost_ms`` (arrival of the next waited-for tick minus the last one before
the hold, less the ticks' usual cadence). 0.0 for a clean window, so that the
ledger shows a clean side beside a held one."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_holds").lost_ms(run)
