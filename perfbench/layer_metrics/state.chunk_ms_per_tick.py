"""Device time of the chunk rows' rule from a carried state, as the cell's
trace helper cuts it (part ``state_chunk``), mean over the traced runs of the
tick program, of which about one in five carries a chunk of 256. Olmo-Hybrid:
``blk/gdn/chunk`` (the kernel ``gdn_chunk`` and the re-laying of its
operands, all linear layers). Falcon-H1: ``blk/ssd/chunk`` (``ssd_chunk``,
nine layers). ``gdn.`` and ``ssd.chunk_ms_per_tick`` until PR 56."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "state_chunk")
