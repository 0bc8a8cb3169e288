"""The whole tick's share of its memory roofline: the least time to move what
one tick must (``yardstick_mla_dense.tick_bytes``: every dense weight and
the head once, the touched experts once, the latents its attention reads,
the rows it writes) over the tick's median device time."""
from perfbench import loader, yardstick_mla_dense


def read(run):
    s = loader.load_module("layer_metrics", "_dsv2_trace").tick_shape(run)
    if s is None:
        return None
    moved = yardstick_mla_dense.tick_bytes(
        run["ctx"].config, s["tokens"], (s["decode"], s["chunk"]),
        s["sampled"], s["touched"])
    return 100.0 * moved / s["peak"].hbm_bytes_per_s * 1e3 / s["ms"]
