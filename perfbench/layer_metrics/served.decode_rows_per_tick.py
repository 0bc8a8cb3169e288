"""Requests decoding in a tick in the three newer backlog cells (dots3,
DeepSeek-V2, Olmo-Hybrid), mean over the window's ticks (gauge
``serving/mixed_rows_decode``, read after every tick)."""


def read(run):
    return run["facts"].get("decode_rows_per_tick")
