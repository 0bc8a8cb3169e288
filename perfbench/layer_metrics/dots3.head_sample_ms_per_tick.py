"""Device time the tick spends outside the blocks on what it names: the
embedding lookup, the final norm and the head over the sliced vocabulary, the
sampling (``tick/embed``, ``tick/head``, ``tick/sample``)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dots3_trace").read_part(
        run, "head_sample")
