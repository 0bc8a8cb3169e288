"""``pool.live_kv_pct.*`` on the latent-attention model's cell: the share of
the full layers' pools' positions (slots x capacity) that hold a live
request's tokens, mean over the window's ticks. The windowed layers' pools
hold the window alone and are sized for it."""


def read(run):
    value = run["facts"].get("live_kv_share")
    return None if value is None else 100.0 * value
