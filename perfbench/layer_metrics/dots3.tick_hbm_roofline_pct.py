"""The whole tick's share of its memory roofline: the least time to move what
one tick must (``yardstick_mla.tick_bytes``: every dense weight and the head
once, the touched experts once, the caches its attention reads, the rows it
writes) over the tick's median device time."""
from perfbench import loader, yardstick_mla


def read(run):
    s = loader.load_module("layer_metrics", "_dots3_trace").tick_shape(run)
    if s is None:
        return None
    moved = yardstick_mla.tick_bytes(
        run["ctx"].config, s["decode"], s["chunks"], s["chunk"],
        s["context"], s["sampled"], s["touched"])
    return 100.0 * moved / s["peak"].hbm_bytes_per_s * 1e3 / s["ms"]
