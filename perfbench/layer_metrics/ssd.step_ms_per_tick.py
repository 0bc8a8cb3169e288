"""Device time of the state-space decode step (``blk/ssd/step``: the kernel
``ssd_step`` over the decode rows, nine layers), mean over the traced runs of
the tick program: a live row reads and writes 4.19 MB of state a layer."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_falcon_h1_trace").read_part(
        run, "ssd_step")
