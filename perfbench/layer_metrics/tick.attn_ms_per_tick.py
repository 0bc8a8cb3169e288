"""Device time a serving tick spends in attention proper (scope ``blk/attn``:
the ragged paged attention and the gathers around it, less the KV scatter
inside it), mean over the traced runs of the tick program."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.read_tick_part(run, "attn")
