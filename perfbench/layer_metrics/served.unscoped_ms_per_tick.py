"""Device time of a served model's tick that none of the program's names
covers: operations under no scope and the gaps between operations inside a
run. The cell's named parts and this add up to the tick's device time."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "unscoped")
