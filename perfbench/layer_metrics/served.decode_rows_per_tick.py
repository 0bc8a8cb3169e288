"""Requests decoding in a tick, mean over the window's ticks (gauge
``serving/mixed_rows_decode``, read after every tick), in every backlog cell
(the long-prompt cell's ``sched.decode_rows_per_tick`` and Falcon-H1's
``fh1.`` copy until PR 56); in Ling's cell every slot, 64: its window is all
decode; in Falcon-H1's near all 80."""


def read(run):
    return run["facts"].get("decode_rows_per_tick")
