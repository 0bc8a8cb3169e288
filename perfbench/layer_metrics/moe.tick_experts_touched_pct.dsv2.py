"""``moe.tick_experts_touched_pct`` in DeepSeek-V2's cell: the share of the
held experts that were given a row in a tick, mean over the expert layers
and the run's ticks, as the ticks report it: their matrices are what the
grouped matmuls must read."""


def read(run):
    f = run["facts"]
    if "tick_group_hit_share" not in f:
        return None
    value = f.get("tick_experts_touched_share")
    return None if value is None else 100.0 * value
