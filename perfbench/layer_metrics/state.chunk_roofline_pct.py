"""The chunked rule's share of its roofline: the chunk rows' products in the
chunked form at unpadded widths and their operands and states moved once
(``chunk_flops`` and ``chunk_bytes`` of the family's own yardstick through its
trace helper's ``least_ms``: ``yardstick_gdn``; ``yardstick_ssd`` at blocks of
``mamba_chunk_size``), the slower of the two, over the device time of the
chunk. The mean tick holds a fifth of a chunk and Falcon-H1's program runs the
chunk row in every tick: this reads low there. ``gdn.`` and
``ssd.chunk_roofline_pct`` until PR 56."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").roofline_pct(
        run, "state_chunk")
