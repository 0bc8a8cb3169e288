"""Decode rows a tick of Laguna's cell carries, mean over the window's ticks
(``served.decode_rows_per_tick``'s reader)."""


def read(run):
    return run["facts"].get("decode_rows_per_tick")
