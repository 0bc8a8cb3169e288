"""Device time between a KDA layer's projections and its delta rule
(``blk/kda/prep``: the short convolution over the carried history, SiLU, q's
and k's l2norm a head, 12,288 channels; ``ops/gdn.gdn_prep_rows``, the pass
Olmo-Hybrid's cell reads as ``gdn.prep_ms_per_tick``), all six KDA layers."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_ling3_trace").read_part(
        run, "kda_prep")
