"""The fullest held expert's rows over the mean held expert's, mean over the
expert layers and the run's ticks, as the ticks report it (dots3's cell and
DeepSeek-V2's)."""


def read(run):
    value = run["facts"].get("tick_expert_load_max_over_mean")
    return None if value is None else value
