"""Requests decoding in a tick, mean over the window's ticks (gauge
``serving/mixed_rows_decode``, read after every tick): near all 80 slots."""


def read(run):
    value = run["facts"].get("decode_rows_per_tick")
    return None if value is None else 1.0 * value
