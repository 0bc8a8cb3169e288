"""Share of the K and V pools' positions (slots x capacity; over all 192
cache layers in the looped model's cell) that hold a live request's tokens,
mean over the window's ticks, in the two open-loop cells: prompt tokens made
resident (the engine's ``chunk`` and ``prefix_hit`` events) plus tokens
emitted, of requests that have not finished. The rest of the pools is
reserved and idle, or parked by the prefix cache; the looped tick's gathers
read the pools capacity-wide, so there the rest is time in
``loop.attn_ms_per_tick`` that no token needed.
"""


def read(run):
    share = run["facts"].get("live_kv_share")
    return None if share is None else 100.0 * share
