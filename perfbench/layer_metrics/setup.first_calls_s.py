"""Every dispatch site's first call before the window (the step, the tick,
the page copy): the phases ``setup/first_call``, from before the call to
its return. Tracing, lowering, and compiling or fetching the program lie in
here; so does the execution the call waits for, if it waits."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_setup").row(run, "first_calls")
