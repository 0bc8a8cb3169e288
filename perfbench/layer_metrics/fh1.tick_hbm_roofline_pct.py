"""The whole tick's share of its memory roofline: the least time to move what
one tick must (``yardstick_ssd.tick_bytes`` through ``_falcon_h1_trace.needs``:
every weight and the head once, the live rows' states both ways, the chunk
rows' state and operands, the K and V its attention reads, what it writes),
over the tick's median device time."""
from perfbench import loader


def read(run):
    needs = loader.load_module("layer_metrics", "_falcon_h1_trace").needs(run)
    if needs is None:
        return None
    s, moved, _ = needs
    return 100.0 * moved / s["peak"].hbm_bytes_per_s * 1e3 / s["ms"]
