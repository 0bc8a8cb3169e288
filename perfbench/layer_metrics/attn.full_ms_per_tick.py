"""Device time of full attention over K/V pages, decode rows and chunk rows, as
the cell's trace helper cuts it (part ``attn``). Olmo-Hybrid: ``blk/attn``,
the ragged paged kernel at 32 head rows, 30 of them heads, in its four full
layers. Falcon-H1: ``blk/attn/full``, ``grouped_paged_attn`` over 4 key/value
heads under 20 query heads, nine layers (``fh1.attn_ms_per_tick`` until
PR 56)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "attn")
