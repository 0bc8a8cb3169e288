"""``pool.live_kv_pct.*`` on the looped model's cell: the share of the K
and V pools' positions (slots x capacity, over all 192 cache layers) that
hold a live request's tokens, mean over the window's ticks. The tick's
gathers read the pools capacity-wide, so the rest is time in
``loop.attn_ms_per_tick`` that no token needed."""


def read(run):
    share = run["facts"].get("live_kv_share")
    return None if share is None else 100.0 * share
