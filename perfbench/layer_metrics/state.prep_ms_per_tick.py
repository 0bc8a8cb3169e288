"""Device time between a state layer's projections and its rule, as the cell's
trace helper cuts it (part ``state_prep``): the short convolution over the
slot's carried history and SiLU, the history read and written in place (the
states themselves move inside the step and chunk kernels). Olmo-Hybrid:
``blk/gdn/prep`` and ``blk/state_io`` (``ops/gdn.gdn_prep_rows``, with q's and
k's l2norm a head). Ling-3.0-flash: ``blk/kda/prep``, the same pass at 12,288
channels, all six KDA layers. Falcon-H1: ``blk/ssd/prep`` (``ssd_prep_step``
and ``ssd_prep_chunk``: a bias and no l2norm, and XLA's cutting around them).
``gdn.prep_ms_per_tick`` (``kda.`` until PR 53) and ``ssd.prep_ms_per_tick``
until PR 56."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "state_prep")
