"""Prompt tokens a tick prefills in the three newer backlog cells (dots3,
DeepSeek-V2, Olmo-Hybrid), where it stands against ``serve_tokens_per_s``:
the engine's prefill rows a tick (gauge ``serving/mixed_rows_prefill``,
read after every tick of the window) times its chunk width."""


def read(run):
    f = run["facts"]
    if "prefill_rows_per_tick" not in f:
        return None
    return f["prefill_rows_per_tick"] * f["prefill_chunk"]
