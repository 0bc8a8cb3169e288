"""Share of the held experts a tick gave at least one row, mean over the
expert layers and the run's ticks, as the ticks report it (dots3's cell,
DeepSeek-V2's and Ling-3.0-flash's: 1 - 1/e, 63 %, where 64 tokens send 2 of
their 8 assignments here at random): what of their weights the grouped
matmuls must read."""


def read(run):
    value = run["facts"].get("tick_experts_touched_share")
    return None if value is None else 100.0 * value
