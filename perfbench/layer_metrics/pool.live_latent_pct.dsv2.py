"""``pool.live_kv_pct.*`` in DeepSeek-V2's cell: the share of the latent
pool's positions (slots x capacity) that hold a live request's tokens, mean
over the window's ticks. There is no other pool: no indexer keys, no window
space."""


def read(run):
    f = run["facts"]
    if "tick_group_hit_share" not in f or "live_kv_share" not in f:
        return None
    return 100.0 * f["live_kv_share"]
