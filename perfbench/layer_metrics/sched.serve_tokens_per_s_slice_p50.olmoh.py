"""``sched.serve_tokens_per_s_slice_p50`` in Olmo-Hybrid's cell, where it stands against
``serve_tokens_per_s``: the accepted reader's list of cells is closed."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "sched.serve_tokens_per_s_slice_p50").read(run)
