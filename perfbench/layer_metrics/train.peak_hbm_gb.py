"""Peak device memory of the process on its fullest chip
(``memory_stats()["peak_bytes_in_use"]`` after the window). It includes the
trainer's build, which at these sizes is the peak."""


def read(run):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in run["ctx"].devices]
    peaks = [p for p in peaks if p]
    return max(peaks) / 1e9 if peaks else None
