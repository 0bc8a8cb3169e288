"""Device time between an SSD layer's projection and its rule
(``blk/ssd/prep``: the short convolution over the slot's carried history, its
bias and SiLU, the history read and written in place by ``ssd_prep_step`` and
``ssd_prep_chunk``, and XLA's cutting around them), mean over the traced runs
of the tick program."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_falcon_h1_trace").read_part(
        run, "ssd_prep")
