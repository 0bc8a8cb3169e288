"""Pages of the windowed layers' pools the engine gave back a tick because no
later query can see them (counter ``serving/window_pages_freed`` over
``serving/ticks``, the whole run): two a chunk of two pages, one a decode row
every ``page_size`` tokens."""


def read(run):
    value = run["facts"].get("window_pages_freed_per_tick")
    return None if value is None else value
