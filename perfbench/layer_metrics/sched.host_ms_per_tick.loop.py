"""``sched.host_ms_per_tick`` on the looped model's cell, where the host's
share of a tick stands against ``itl_p95_ms``: the cell judges no time to
first token (PERF.md section 4), so the accepted reader's list is closed
to it."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "sched.host_ms_per_tick").read(
        run)
