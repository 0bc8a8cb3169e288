"""The dense latent attention's share of its roofline: the least time of the
tick's attention calls (``yardstick_mla_dense.attention_least_ms``: for each
call the live latents read once and the lesser of the absorbed and the
expanded form's operations, the slower of moving and multiplying, as the
ticks counted their visible pairs and live keys) over the device time of
``blk/attn/mla_chunk`` and ``blk/attn/mla_decode`` together. It reads the
same work whatever implements it."""
from perfbench import loader, yardstick_mla_dense


def read(run):
    tr = loader.load_module("layer_metrics", "_dsv2_trace")
    s = tr.tick_shape(run)
    if s is None:
        return None
    ms = tr.read_part(run, "mla_chunk") + tr.read_part(run, "mla_decode")
    if not ms:
        return None
    least = yardstick_mla_dense.attention_least_ms(
        run["ctx"].config, (s["decode"], s["chunk"]), s["peak"])
    return 100.0 * least / ms
