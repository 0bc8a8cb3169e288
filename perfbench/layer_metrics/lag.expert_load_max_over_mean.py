"""The fullest held expert's rows over the mean held expert's in Laguna's
ticks, mean over the expert layers and the run's ticks
(``moe.tick_expert_load_max_over_mean``'s reader)."""


def read(run):
    value = run["facts"].get("tick_expert_load_max_over_mean")
    return None if value is None else value
