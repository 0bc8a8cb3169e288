"""Share of the state pool's slots that hold a tenant's state (the gauge
``serving/live_pages{pool=state}`` as the run's last tick left it): a state
is a slot's whatever the context, so this is the share of the pool in use
(Olmo-Hybrid: 1.1 GB; Ling-3.0-flash: 12.6 MB a slot over six KDA layers;
Falcon-H1: 37.75 MB a slot over nine layers, ``fh1.live_state_slots_pct``
until PR 56)."""


def read(run):
    share = run["facts"].get("live_state_share")
    return None if share is None else 100.0 * share
