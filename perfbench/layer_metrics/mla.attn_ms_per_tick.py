"""Device time the tick spends in the full layers' absorbed attention over the
selected latents, their fetch included (``blk/attn/mla``)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dots3_trace").read_part(
        run, "mla")
