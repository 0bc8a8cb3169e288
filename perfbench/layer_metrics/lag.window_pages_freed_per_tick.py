"""Pages of Laguna's windowed layers' page space the engine gave back a tick
because no later query can see them (counter ``serving/window_pages_freed``
over ``serving/ticks``; ``pool.window_pages_freed_per_tick``'s reader): 16 a
chunk of 16 pages, one a decode row every 16 tokens."""


def read(run):
    value = run["facts"].get("window_pages_freed_per_tick")
    return None if value is None else value
