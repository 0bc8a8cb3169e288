"""``moe.tick_expert_load_max_over_mean`` in DeepSeek-V2's cell: the fullest
held expert's rows over the mean held expert's, mean over the expert layers
and the run's ticks, as the ticks report it. The family's own fact is asked
for first, so that a program whose ticks report no group limit reads
nothing here."""


def read(run):
    f = run["facts"]
    if "tick_group_hit_share" not in f:
        return None
    return f.get("tick_expert_load_max_over_mean")
