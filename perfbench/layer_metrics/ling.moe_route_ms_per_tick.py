"""Device time of the router over 512 experts (``moe/route``: scores, the group
limit by the sum of a group's two best biased scores, eight rounds of
argmax, the counting sort of the held assignments, and the tick's own
statistics of its routing), six expert layers."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_ling3_trace").read_part(
        run, "route")
