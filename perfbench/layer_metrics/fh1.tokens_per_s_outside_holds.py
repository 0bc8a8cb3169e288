"""The run's own ``serve_tokens_per_s`` times ``seconds / (seconds - lost)``:
the rate the window would have read without its holds
(``_holds.tokens_per_s_outside``)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_holds").tokens_per_s_outside(run)
