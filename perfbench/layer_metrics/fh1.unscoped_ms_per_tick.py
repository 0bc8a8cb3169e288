"""Device time of Falcon-H1's tick that none of the program's names covers:
operations outside every scope (the weights' prefetch, copies XLA adds between
the parts) and the gaps in which no operation runs."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_falcon_h1_trace").read_part(
        run, "unscoped")
