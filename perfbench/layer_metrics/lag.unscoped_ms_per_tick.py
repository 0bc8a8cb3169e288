"""Device time of Laguna's tick under none of the program's names, operation
or gap: ``served.unscoped_ms_per_tick``'s reader."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_laguna_trace").read_part(
        run, "unscoped")
