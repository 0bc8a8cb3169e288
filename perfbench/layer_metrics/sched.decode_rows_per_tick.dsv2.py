"""``sched.decode_rows_per_tick`` in DeepSeek-V2's cell, where it stands against
``serve_tokens_per_s``: the accepted reader's list of cells is closed."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "sched.decode_rows_per_tick").read(run)
