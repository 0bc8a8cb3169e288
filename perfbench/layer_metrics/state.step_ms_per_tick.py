"""Device time of a recurrent state's decode step, as the cell's trace helper
cuts it (part ``state_step``): one token a live row against the row's float32
state, read and written in place at its slot, all the layers that keep a
state. Olmo-Hybrid: ``blk/gdn/step``, the kernel ``gdn_step`` (a gated delta
rule, a scalar gate a head; twelve linear layers). Ling-3.0-flash:
``blk/kda/step``, ``kda_step`` (a decay a key channel; 32 heads of 128 x 128,
six layers). Falcon-H1: ``blk/ssd/step``, ``ssd_step`` (the state-space rule;
4.19 MB of state a row and layer, nine layers). ``gdn.``, ``kda.`` and
``ssd.step_ms_per_tick`` until PR 56."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "state_step")
