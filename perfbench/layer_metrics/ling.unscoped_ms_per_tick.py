"""Device time of the tick under none of the program's names, and the time
inside the tick's runs in which no operation ran."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_ling3_trace").read_part(
        run, "unscoped")
