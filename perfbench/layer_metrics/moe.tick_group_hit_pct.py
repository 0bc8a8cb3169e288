"""Share of a tick's live tokens whose kept groups of experts include a group
this chip holds, mean over the expert layers and the run's ticks, as the
ticks report it (``group_hit_share``). DeepSeek-V2: 3 kept of 8, one held:
3 of 8, 37.5 %, where the router is balanced. Ling-3.0-flash: 4 kept of 8,
two held: 1 - C(6,4)/C(8,4), 78.6 %. Only such a token can send a row to a
held expert."""


def read(run):
    value = run["facts"].get("tick_group_hit_share")
    return None if value is None else 100.0 * value
