"""The decode rows' dense latent attention's share of its roofline: the least
time of the call (``yardstick_ling3.attention_least_ms``: the rows' live
latents read once, 1,152 B each, and the lesser of the absorbed and the
expanded form's operations, the slower of moving and multiplying, as the
ticks counted their visible pairs and live keys) over the device time of
``blk/mla/decode``."""
from perfbench import loader, yardstick_ling3 as y


def read(run):
    return loader.load_module("layer_metrics", "_ling3_trace").roofline_pct(
        run, "mla_decode", lambda c, s, peak: y.attention_least_ms(
            c, (s["decode"],), peak))
