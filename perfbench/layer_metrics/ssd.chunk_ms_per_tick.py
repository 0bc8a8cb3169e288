"""Device time of the chunked state-space scan (``blk/ssd/chunk``: the kernel
``ssd_chunk`` over the chunk row, nine layers), mean over the traced runs of
the tick program, of which about one in five carries a chunk of 256."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_falcon_h1_trace").read_part(
        run, "ssd_chunk")
