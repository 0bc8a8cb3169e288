"""Device time a looped model's serving tick spends in operations under none
of the program's scope names: what XLA adds around the two scans, mean over
the traced runs of the tick program."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_loop_trace").read_part(
        run, "unscoped")
