"""Requests decoding in a tick in the four newer backlog cells (dots3,
DeepSeek-V2, Olmo-Hybrid, Ling-3.0-flash), mean over the window's ticks
(gauge ``serving/mixed_rows_decode``, read after every tick); in Ling's cell
every slot, 64: its window is all decode."""


def read(run):
    return run["facts"].get("decode_rows_per_tick")
