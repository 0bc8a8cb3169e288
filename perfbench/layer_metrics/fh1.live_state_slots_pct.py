"""Share of the state pool's slots that hold a tenant's state (the gauge
``serving/live_pages{pool=state}`` as the run's last tick left it): a state is
a slot's whatever the context, 37.75 MB over nine layers."""


def read(run):
    value = run["facts"].get("live_state_share")
    return None if value is None else 100.0 * value
