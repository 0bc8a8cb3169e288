"""Device time a tick spends in operations whose result is a whole page
pool ``[L, P, page, heads, head_dim]``, matched by shape in the trace."""
from perfbench import loader, tracered


def read(run):
    doc, dims = run["ctx"].trace_doc, run["facts"].get("pool_dims")
    if doc is None or dims is None:
        return None
    ticks = len(loader.load_module("layer_metrics", "_tick")
                .tick_runs_ms(doc))
    if not ticks:
        return None
    return tracered.whole_pool_ops_s(doc, dims) * 1e3 / ticks
