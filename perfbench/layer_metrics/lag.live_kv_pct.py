"""Share of Laguna's full layers' positions (38 slots x 17,664) that hold a
live request's tokens, mean over the window's ticks
(``pool.live_kv_pct.backlog``'s reader)."""


def read(run):
    share = run["facts"].get("live_kv_share")
    return None if share is None else 100.0 * share
