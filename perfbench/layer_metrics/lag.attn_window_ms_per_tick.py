"""Device time Laguna's tick spends in its four windowed layers' attention
(``blk/attn/window``: 72 query heads over the same 8 key/value heads, at most
512 keys a query, the walk from the page of the oldest visible key)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_laguna_trace").read_part(
        run, "attn_window")
