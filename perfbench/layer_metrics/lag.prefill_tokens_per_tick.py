"""Prompt tokens a tick of Laguna's cell prefills: the engine's prefill rows a
tick times its chunk width (``served.prefill_tokens_per_tick``'s reader)."""


def read(run):
    f = run["facts"]
    if "prefill_rows_per_tick" not in f:
        return None
    return f["prefill_rows_per_tick"] * f["prefill_chunk"]
