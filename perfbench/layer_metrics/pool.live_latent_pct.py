"""``pool.live_kv_pct.*`` in the two latent-attention cells: the share of the
latent pools' positions (slots x capacity) that hold a live request's
tokens, mean over the window's ticks. dots3: the full layers' pools (the
windowed layers' pools hold the window alone and are sized for it);
DeepSeek-V2: its one pool (no indexer keys, no window space)."""


def read(run):
    value = run["facts"].get("live_kv_share")
    return None if value is None else 100.0 * value
