"""Share of a tick's live tokens whose 4 kept groups of experts include one of
the two this chip holds, mean over the expert layers and the run's ticks, as
the ticks report it (``group_hit_share``): 1 - C(6,4)/C(8,4), 78.6 %, where
the router is balanced. Only such a token can send a row to a held expert."""


def read(run):
    value = run["facts"].get("tick_group_hit_share")
    return None if value is None else 100.0 * value
