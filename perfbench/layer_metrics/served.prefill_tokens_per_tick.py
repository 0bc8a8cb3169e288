"""Prompt tokens a tick prefills in the backlog cells whose window prefills
(all but Ling-3.0-flash's; the long-prompt cell's
``sched.prefill_tokens_per_tick`` and Falcon-H1's ``fh1.`` copy until PR 56),
where it stands against ``serve_tokens_per_s``:
the engine's prefill rows a tick (gauge ``serving/mixed_rows_prefill``,
read after every tick of the window) times its chunk width."""


def read(run):
    f = run["facts"]
    if "prefill_rows_per_tick" not in f:
        return None
    return f["prefill_rows_per_tick"] * f["prefill_chunk"]
