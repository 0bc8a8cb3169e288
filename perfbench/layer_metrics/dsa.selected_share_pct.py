"""Of the keys a live query may see, the share its indexer selected: mean over
the live queries of a tick and over the run's ticks, as the ticks report it
(``index_topk`` over the context once the context is longer)."""


def read(run):
    value = run["facts"].get("tick_selected_share")
    return None if value is None else 100.0 * value
