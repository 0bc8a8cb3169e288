"""Device time a served model's tick spends outside the blocks on what it
names: the embedding rows, the final norm, the head (over the vocabulary's
slice in the dots3 and DeepSeek-V2 cells, over 100,352 words in
Olmo-Hybrid's, over the quarter of the vocabulary held in Ling-3.0-flash's,
over the eighth held in Falcon-H1's) and the sampling (``tick/embed``, ``tick/head``,
``tick/sample``)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "head_sample")
