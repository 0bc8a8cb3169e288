"""Device time of the full layers' attention over K/V pages (``blk/attn``: the
ragged paged kernel at 32 head rows, 30 of them heads), decode rows and
chunk rows."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_olmoh_trace").read_part(
        run, "attn")
