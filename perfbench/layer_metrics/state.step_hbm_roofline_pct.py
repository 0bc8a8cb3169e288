"""The decode step's share of its memory roofline, whatever the rule: the live
rows' states read and written once and their operands, unpadded, at the chip's
HBM peak (the family's own yardstick through its trace helper's ``least_ms``:
``step_bytes`` of ``yardstick_gdn``, 2 x 2.21 MB a row and linear layer;
of ``yardstick_ling3``, 2 x 2.10 MB a row and KDA layer; of ``yardstick_ssd``,
2 x 4.19 MB a row and layer), over the device time of the step. It reads the
same work whatever implements it. ``gdn.``, ``kda.`` and
``ssd.step_hbm_roofline_pct`` until PR 56."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").roofline_pct(
        run, "state_step")
