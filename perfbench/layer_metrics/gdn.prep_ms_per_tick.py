"""Device time between a linear layer's projections and its delta rule, as the
cell's trace helper cuts it (part ``gdn_prep``: ``ops/gdn.gdn_prep_rows``, the
short convolution over the carried history, SiLU, q's and k's l2norm a head;
the states themselves move inside the step and chunk kernels). Olmo-Hybrid:
``blk/gdn/prep`` and ``blk/state_io`` (that history gathered and scattered by
slot). Ling-3.0-flash: ``blk/kda/prep``, 12,288 channels, all six KDA layers
(``kda.prep_ms_per_tick`` until PR 53)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "gdn_prep")
