"""The fullest held expert's rows over the mean held expert's, mean over the
expert layers and the run's ticks, as the ticks report it (dots3's cell,
DeepSeek-V2's and Ling-3.0-flash's: there about 128 rows over 128 experts a
tick, a Poisson's fullest, 4 to 5, and no sign of a bias that chooses)."""


def read(run):
    value = run["facts"].get("tick_expert_load_max_over_mean")
    return None if value is None else value
