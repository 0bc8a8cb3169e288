"""Median wait from a request's due time to the dispatch of its first
prefill chunk (the engine's ``chunk`` event, stamped on the benchmark's
clock after the step that dispatched it)."""
from perfbench import yardstick


def read(run):
    waits = run["facts"].get("queue_wait_ms")
    return yardstick.percentile(waits, 50) if waits else None
