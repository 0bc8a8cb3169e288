"""The trainer's or the engine's constructor: the phases ``setup/trainer``
or ``setup/engine`` and their children (stacking and placing the
parameters, the optimizer's state, freeing the eager copies; the served
state, the page pools), less any first call and any weights drawn
inside."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_setup").row(run, "build")
