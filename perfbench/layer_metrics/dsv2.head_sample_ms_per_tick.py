"""Device time DeepSeek-V2's tick spends on the embedding rows, the final norm, the
head over the vocabulary's slice and the sampling (``tick/embed``, ``tick/head``,
``tick/sample``)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_dsv2_trace").read_part(
        run, "head_sample")
