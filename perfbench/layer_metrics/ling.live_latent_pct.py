"""Share of the latent pages' positions (64 slots x 17,024) that hold a live
request's tokens, mean over the window's ticks: the one MLA layer's cache,
1,152 B a token."""


def read(run):
    value = run["facts"].get("live_kv_share")
    return None if value is None else 100.0 * value
