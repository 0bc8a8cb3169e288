"""The whole tick's share of its memory roofline: the least time to move what
one tick must (``yardstick_ling3.tick_bytes`` through ``_ling3_trace.needs``:
every dense weight and the head once, the experts **touched** once, the live
rows' states both ways, the latents its attention reads, what it writes),
over the tick's median device time."""
from perfbench import loader


def read(run):
    needs = loader.load_module("layer_metrics", "_ling3_trace").needs(run)
    if needs is None:
        return None
    s, moved, _ = needs
    return 100.0 * moved / s["peak"].hbm_bytes_per_s * 1e3 / s["ms"]
