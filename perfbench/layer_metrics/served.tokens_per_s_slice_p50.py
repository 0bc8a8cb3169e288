"""Median rate over the equal consecutive slices the traffic file cuts the
window into, in every backlog cell (the long-prompt cell's
``sched.serve_tokens_per_s_slice_p50`` and Falcon-H1's ``fh1.`` copy until
PR 56). ``serve_tokens_per_s`` is all progress over all time; this
stands beside it and passes over a slice that a stall spoils, so the two
apart say that the window was not even."""


def read(run):
    return run["facts"].get("serve_tokens_per_s_slice_p50")
