"""Median device time of one run of Falcon-H1's tick program
(``_tick.device_ms_p50``; a run whose tick ``_falcon_h1_trace`` does not read
gives nothing): 80 decode rows and, in about one tick of five, a chunk of
256."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_falcon_h1_trace").tick_ms(run)
