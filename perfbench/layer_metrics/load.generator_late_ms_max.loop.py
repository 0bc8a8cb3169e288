"""``load.generator_late_ms_max`` on the looped model's cell: the longest
the generator submitted a request after it was due, which is inside
``loop.ttft_p85_ms``."""


def read(run):
    return run["facts"].get("generator_late_ms_max")
