"""Device time of the served per-channel delta rule's decode step
(``blk/kda/step``: the kernel ``kda_step``, one token a live row against a
float32 state of 32 heads of 128 x 128, read and written in place), all six
KDA layers."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_ling3_trace").read_part(
        run, "kda_step")
