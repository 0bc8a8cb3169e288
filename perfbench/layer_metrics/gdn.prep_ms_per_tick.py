"""Device time between a linear layer's projections and its delta rule
(``blk/gdn/prep``: the short convolution over the carried history, SiLU, q's
and k's l2norm a head; ``blk/state_io``: that history gathered and scattered
by slot; the states themselves move inside ``gdn_step`` and ``gdn_chunk``)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_olmoh_trace").read_part(
        run, "gdn_prep")
