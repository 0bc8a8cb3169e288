"""The 85th percentile of the times to first token of the requests due in
the window, as ``serve_loop`` computes ``ttft_p85_ms``: six to eight ticks
of a prompt's chunks through the loop. Read in every traced run and not
judged: 25 requests a window spread it by 3.8-4.7 % over seeds, above
what admits an end-to-end metric (PERF.md section 4)."""
from perfbench import yardstick


def read(run):
    ttft = run["facts"].get("ttft_ms")
    return yardstick.percentile(ttft, 85) if ttft else None
