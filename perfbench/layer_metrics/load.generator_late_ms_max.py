"""The longest the load generator submitted a request after it was due.
Times to first token run from the due time, so lateness is inside them;
this says how much of them is the generator's."""


def read(run):
    return run["facts"].get("generator_late_ms_max")
