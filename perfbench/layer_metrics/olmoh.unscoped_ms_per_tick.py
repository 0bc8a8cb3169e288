"""Device time of Olmo-Hybrid's tick that none of the program's names covers:
operations under no scope and the gaps between operations inside a run. The
named parts and this add up to the tick's device time."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_olmoh_trace").read_part(
        run, "unscoped")
