"""The whole tick's share of its memory roofline: the least time to move what
one tick must, by the family's own yardstick (its trace helper's
``tick_needs``: ``yardstick_mla.tick_bytes`` for dots3,
``yardstick_mla_dense.tick_bytes`` for DeepSeek-V2: every dense weight and
the head once, the touched experts once, the caches its attention reads, the
rows it writes; ``yardstick_gdn.tick_bytes`` for Olmo-Hybrid: every weight
and the head once, the live rows' states both ways, the K and V its
attention reads, what it writes; ``yardstick_ling3.tick_bytes`` for
Ling-3.0-flash: every dense weight and the head once, the experts touched
once, the live rows' states both ways, the latents its attention reads, what
it writes; ``yardstick_ssd.tick_bytes`` for Falcon-H1: every weight and the
head once, the live rows' states both ways, the chunk rows' state and
operands, the K and V its attention reads, what it writes), over the tick's
median device time."""
from perfbench import loader


def read(run):
    needs = loader.load_module("layer_metrics", "_served").tick_needs(run)
    if needs is None:
        return None
    s, moved, _ = needs
    return 100.0 * moved / s["peak"].hbm_bytes_per_s * 1e3 / s["ms"]
