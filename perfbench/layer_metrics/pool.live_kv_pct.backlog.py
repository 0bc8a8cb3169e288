"""Share of the K and V pools' positions (slots x capacity) that hold a
live request's tokens, mean over the window's ticks: prompt tokens made
resident (the engine's ``chunk`` and ``prefix_hit`` events) plus tokens
emitted, of requests that have not finished. The rest of the pools is
reserved and idle, or parked by the prefix cache. The long-prompt cell's GPT
pools and Falcon-H1's nine layers of grouped pages (80 slots x 1,408,
18,432 B a token; ``fh1.live_kv_pct`` until PR 56)."""


def read(run):
    share = run["facts"].get("live_kv_share")
    return None if share is None else 100.0 * share
