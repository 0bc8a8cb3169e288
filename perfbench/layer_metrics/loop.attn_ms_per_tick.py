"""Device time a looped model's serving tick spends in attention and the KV
scatter (scopes ``blk/attn``, ``blk/kv_scatter``) over all (step, layer)
caches, mean over the traced runs of the tick program."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_loop_trace").read_part(
        run, "attn")
