"""Share of the K/V pages' positions (80 slots x 1,408) that hold a live
request's tokens, mean over the window's ticks: nine layers' grouped pages,
18,432 B a token."""


def read(run):
    value = run["facts"].get("live_kv_share")
    return None if value is None else 100.0 * value
