"""The process's age at the first line of the program's package (the gauge
``proc/age_at_import_s``, from /proc as ``harness.process_age_s`` reads
it): the interpreter, jax, the TPU runtime's start and the benchmark's own
files. Not the program's to change, and now a number."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_setup").row(run, "before_program")
