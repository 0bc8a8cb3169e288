"""Median rate over the equal consecutive slices the traffic file cuts the
window into, in the four newer backlog cells (dots3, DeepSeek-V2,
Olmo-Hybrid, Ling-3.0-flash). ``serve_tokens_per_s`` is all progress over all time; this
stands beside it and passes over a slice that a stall spoils, so the two
apart say that the window was not even."""


def read(run):
    return run["facts"].get("serve_tokens_per_s_slice_p50")
