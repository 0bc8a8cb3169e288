"""Median rate over the nine equal consecutive slices the traffic file cuts the
window into. ``serve_tokens_per_s`` is all progress over all time; this
stands beside it and passes over a slice that a stall spoils."""


def read(run):
    value = run["facts"].get("serve_tokens_per_s_slice_p50")
    return None if value is None else 1.0 * value
