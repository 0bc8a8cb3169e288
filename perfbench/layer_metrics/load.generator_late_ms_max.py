"""The longest the load generator submitted a request after it was due, in
the two open-loop cells. Times to first token run from the due time, so
lateness is inside ``sched.ttft_p85_ms``; this says how much of it is the
generator's, and a run that reads 50 ms or more here was held by its host.
"""


def read(run):
    return run["facts"].get("generator_late_ms_max")
