"""Device time of the decode rows' delta-rule step (``blk/gdn/step``: the kernel
``gdn_step`` on the chip), all linear layers of a tick."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_olmoh_trace").read_part(
        run, "gdn_step")
