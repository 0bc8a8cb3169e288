"""Requests decoding in a tick, mean over the window's ticks (gauge
``serving/mixed_rows_decode``, read after every tick)."""


def read(run):
    return run["facts"].get("decode_rows_per_tick")
