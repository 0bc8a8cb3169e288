"""The held experts' share of their memory roofline in a serving tick: the time
to read, once, the matrices of the held experts that were given a row (the
ticks' own count, ``experts_touched_share``; the family's own yardstick,
its trace helper's ``experts_bytes``), over ``moe.tick_experts_ms_per_tick``.
With about 8 rows an expert (dots3), 20 (DeepSeek-V2) or one
(Ling-3.0-flash) the products are bound by the weights' bytes, not by
arithmetic."""
from perfbench import loader


def read(run):
    needs = loader.load_module("layer_metrics", "_served").experts_needs(run)
    if needs is None:
        return None
    s, moved, ms = needs
    least = moved / s["peak"].hbm_bytes_per_s * 1e3
    return 100.0 * least / ms
