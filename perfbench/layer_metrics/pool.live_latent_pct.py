"""``pool.live_kv_pct.*`` in the cells that keep latent pages: the share of the
latent pools' positions (slots x capacity) that hold a live request's
tokens, mean over the window's ticks. dots3: the full layers' pools (the
windowed layers' pools hold the window alone and are sized for it);
DeepSeek-V2: its one pool (no indexer keys, no window space);
Ling-3.0-flash: the one MLA layer's pages (64 slots x 17,024 positions,
1,152 B a token)."""


def read(run):
    value = run["facts"].get("live_kv_share")
    return None if value is None else 100.0 * value
