"""The sparse absorbed attention's share of its roofline: the least time for
``index_topk`` latents a query, fetched by each query and scored and weighed
by every head (``yardstick_mla.mla_ops_bytes``), over ``mla.attn_ms_per_tick``."""
from perfbench import loader, yardstick_mla


def read(run):
    return loader.load_module("layer_metrics", "_dots3_trace").roofline_pct(
        run, "mla", yardstick_mla.mla_ops_bytes)
