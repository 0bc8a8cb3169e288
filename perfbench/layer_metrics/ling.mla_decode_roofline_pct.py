"""The decode rows' dense latent attention's share of its roofline: the least
time of the call (``yardstick_ling3.attention_least_ms``: the rows' live
latents read once, 1,152 B each, and the lesser of the absorbed and the
expanded form's operations, the slower of moving and multiplying, as the
ticks counted their visible pairs and live keys) over the device time of
``blk/mla/decode``. Through ``_served.roofline_pct``: the cell's helper hands
out the floor (``_ling3_trace.least_ms``), DeepSeek-V2's hands out none."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").roofline_pct(
        run, "mla_decode")
