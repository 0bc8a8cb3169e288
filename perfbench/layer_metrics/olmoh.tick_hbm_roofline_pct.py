"""The whole tick's share of its memory roofline: the least time to move what
one tick must (``yardstick_gdn.tick_bytes``: every weight and the head once,
the live rows' states both ways, the K and V its attention reads, what it
writes) over the tick's median device time."""
from perfbench import loader, yardstick_gdn


def read(run):
    s = loader.load_module("layer_metrics", "_olmoh_trace").tick_shape(run)
    if s is None:
        return None
    moved = yardstick_gdn.tick_bytes(run["ctx"].config, s)
    return 100.0 * moved / s["peak"].hbm_bytes_per_s * 1e3 / s["ms"]
