"""Device memory in use on the fullest chip between steps, after the
window (``memory_stats()["bytes_in_use"]``): parameters, moments and
whatever else outlives a step. ``train.peak_hbm_gb`` beside it is the
process's peak, which the trainer's build sets; a step's own temporaries
are in neither and come from the compiler's memory analysis (PERF.md)."""


def read(run):
    live = run["facts"].get("live_bytes")
    return live / 1e9 if live else None
