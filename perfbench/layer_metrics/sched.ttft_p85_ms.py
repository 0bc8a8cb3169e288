"""The 85th percentile of the times to first token of the requests due in
the window, as ``serve_loop`` computes ``ttft_p85_ms``, in the two open-loop
cells. Read in every traced run and not judged. ``serve-chat-steady``: 72
requests a window; the tail is sparse about rank 62 (66.7, 68.7, 72.2, 72.7
ms), so one request held 50 ms by the host moves the number a rank, 5 %, in
three runs of five, more than the contract's widest bound admits (PERF.md
section 2; it was an end-to-end metric until PR 48).
``serve-ouro-reason-steady``: six to eight ticks of a prompt's chunks through
the loop, 25 requests a window, 3.8-4.7 % over seeds (it was
``loop.ttft_p85_ms``). Stands against ``itl_p95_ms`` because a per-layer
metric names a metric its cells report.
"""
from perfbench import yardstick


def read(run):
    ttft = run["facts"].get("ttft_ms")
    return yardstick.percentile(ttft, 85) if ttft else None
