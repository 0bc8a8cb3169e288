"""Device time of the embedding, the final norm, the head over the vocabulary's
slice and sampling (``tick/embed``, ``tick/head``, ``tick/sample``), mean
over the traced runs of Falcon-H1's tick program."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_falcon_h1_trace").read_part(
        run, "head_sample")
