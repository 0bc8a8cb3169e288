"""Median wait from a request's due time to the dispatch of its first
prefill chunk (the engine's ``chunk`` event, stamped on the benchmark's
clock after the step that dispatched it), in the two open-loop cells
(``serve-chat-steady`` and, on the looped path, ``serve-ouro-reason-steady``).
Read with ``sched.ttft_p85_ms``, so that a change to queueing or chunked
prefill shows in the ledger although no cell judges a time to first token;
listed under ``itl_p95_ms`` because a per-layer metric names a metric its
cells report.
"""
from perfbench import yardstick


def read(run):
    waits = run["facts"].get("queue_wait_ms")
    return yardstick.percentile(waits, 50) if waits else None
