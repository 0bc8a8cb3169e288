"""Device time a looped model's serving tick spends under ``loop/exit``: each
step's final norm, the exit gate and the choice of the step the head reads,
mean over the traced runs of the tick program."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_loop_trace").read_part(
        run, "exit")
