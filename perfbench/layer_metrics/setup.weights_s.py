"""The host's seconds making the parameters: the counters
``setup/weights_s{where=host}`` (the eager draw, a parameter at a time, in
``create_parameter``), ``{where=device}`` (the one jitted draw of a model
built under ``LazyGuard``) and ``setup/cast_s`` (``Layer.bfloat16`` and its
kin), wherever they were drawn: what was drawn inside a phase is taken out
of that phase's row (``_setup.py``)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_setup").row(run, "weights")
