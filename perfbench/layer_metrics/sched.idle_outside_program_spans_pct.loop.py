"""``sched.idle_outside_program_spans_pct`` on the looped model's cell: of
the device's idle time between ticks of 56 ms, the share the engine's
``pt:`` spans do not explain. It stands against ``itl_p95_ms`` there; the
cell judges no time to first token (PERF.md section 4)."""
from perfbench import loader


def read(run):
    return loader.load_module(
        "layer_metrics", "sched.idle_outside_program_spans_pct").read(run)
