"""Full attention's share of its roofline: the key/value heads' K and V of the
rows' live keys read once and 4 x 128 operations a visible pair and query head
(``attention_bytes`` and ``attention_flops`` of the family's own yardstick
through its trace helper's ``least_ms``), the slower of the two, over the
device time of the part. Each cell's floor is its own yardstick's:
``yardstick_gdn``'s 30 heads for Olmo-Hybrid (the pools' two head rows of
zeros are not counted: they are the implementation's), ``yardstick_ssd``'s 4
grouped heads for Falcon-H1, where 80 rows of ~660 keys at 2,048 B a key and
layer are little to move, the walk over pages of 16 binds and this reads low
(``fh1.attn_roofline_pct`` until PR 56)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").roofline_pct(
        run, "attn")
