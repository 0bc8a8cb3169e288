"""``sched.queue_wait_p50_ms`` on the looped model's cell: the median wait
from a request's due time to its first prefill chunk. Read, with
``loop.ttft_p85_ms``, so that a change to queueing or chunked prefill on
the looped path shows in the ledger although the cell judges no time to
first token; listed under ``itl_p95_ms`` because a per-layer metric names
a metric its cell reports."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "sched.queue_wait_p50_ms").read(
        run)
