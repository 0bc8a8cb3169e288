"""Prompt tokens a tick prefills, mean over the window's ticks: the engine's
prefill rows a tick (gauge ``serving/mixed_rows_prefill``, read after every
tick) times its chunk width."""


def read(run):
    f = run["facts"]
    if "prefill_rows_per_tick" not in f:
        return None
    return f["prefill_rows_per_tick"] * f["prefill_chunk"]
