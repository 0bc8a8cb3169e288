"""Device time Olmo-Hybrid's tick spends on the embedding rows, the final norm,
the head over 100,352 words and the sampling (``tick/embed``, ``tick/head``,
``tick/sample``)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_olmoh_trace").read_part(
        run, "head_sample")
