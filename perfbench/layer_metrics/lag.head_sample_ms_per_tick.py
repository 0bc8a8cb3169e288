"""Device time Laguna's tick spends in the embedding, the last norm, the head
over the slice's 25,088 rows and sampling: ``served.head_sample_ms_per_tick``'s
reader."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_laguna_trace").read_part(
        run, "head_sample")
