"""Share of the held experts a tick gave at least one row, mean over the
expert layers and the run's ticks: what of their weights a tick must read."""


def read(run):
    value = run["facts"].get("tick_experts_touched_share")
    return None if value is None else 100.0 * value
