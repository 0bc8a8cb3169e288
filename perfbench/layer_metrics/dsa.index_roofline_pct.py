"""The indexer's scores' share of their roofline: the least time the chip could
take to score every query of the mean tick against the keys it may see
(``yardstick_mla.index_ops_bytes``: a row's live keys read once, 64 products of
128 a pair), over ``dsa.index_ms_per_tick``."""
from perfbench import loader, yardstick_mla


def read(run):
    return loader.load_module("layer_metrics", "_dots3_trace").roofline_pct(
        run, "index", yardstick_mla.index_ops_bytes)
