"""Seconds the backend spent compiling programs the cache did not hold,
before the window: the counter ``compile/backend_s`` as it stood at the
window's opening. Cuts across the rows: inside ``setup.first_calls_s`` and,
for eager operations' small programs, inside ``setup.weights_s`` and
``setup.build_s``."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_setup").compile_total(
        run, "backend_s")
