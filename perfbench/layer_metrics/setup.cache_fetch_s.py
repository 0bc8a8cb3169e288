"""Seconds spent fetching programs from the persistent compile cache
(key, read, deserialization) before the window: the counter
``compile/cache_fetch_s`` as it stood at the window's opening. Cuts across
the rows, as ``setup.backend_compile_s`` does."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_setup").compile_total(
        run, "cache_fetch_s")
