"""Of the excess milliseconds of all the window's holds, the share that the
thread's own counters do not cover (``unexplained_ms``: what is left after
collector and run-queue time; a device hold whole). To the engine's record
what ``sched.idle_outside_program_spans_pct`` is to the spans: what the
instrumentation does not explain yet. 0.0 for a window without a hold."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_holds").unexplained_pct(run)
