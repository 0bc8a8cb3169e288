"""Laguna's cell's tokens a second over the window less its holds
(``served.tokens_per_s_outside_holds``'s reader)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics",
                              "_holds").tokens_per_s_outside(run)
