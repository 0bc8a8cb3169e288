"""Programs compiled or fetched before the window, hits and misses, the
eager operations' small ones included: the counter ``compile/programs`` as
it stood at the window's opening."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_setup").compile_total(
        run, "programs")
