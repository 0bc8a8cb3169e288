"""Share of Laguna's 64 held experts a layer that a tick gave at least one
row (``moe.tick_experts_touched_pct``'s reader): what of their weights the
grouped matmuls must read."""


def read(run):
    value = run["facts"].get("tick_experts_touched_share")
    return None if value is None else 100.0 * value
