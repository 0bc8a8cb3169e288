"""Device time of the embedding's rows, the last norm, the head over the
quarter of the vocabulary held and the sampling (``tick/embed``,
``tick/head``, ``tick/sample``)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_ling3_trace").read_part(
        run, "head_sample")
