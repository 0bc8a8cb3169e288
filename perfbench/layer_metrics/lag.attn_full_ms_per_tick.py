"""Device time Laguna's tick spends in its two full layers' attention
(``blk/attn/full``: 48 query heads over 8 key/value heads, decode rows against
their whole context and the chunk in pieces; ``attn.full_ms_per_tick``'s
reader)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_laguna_trace").read_part(
        run, "attn")
