"""Device time of the chunk rows' delta rule from a carried state
(``blk/gdn/chunk``: the kernel ``gdn_chunk`` on the chip and the re-laying of
its operands), all linear layers, mean over the ticks traced (one in five
carries a chunk)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_olmoh_trace").read_part(
        run, "gdn_chunk")
