"""Device time a serving tick spends in what is outside the blocks (scopes
``tick/embed``, ``tick/head``, ``tick/sample``: embedding, final norm and
lm-head, sampling and the token fold), mean over the traced runs of the tick
program."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.read_tick_part(run, "head_sample")
