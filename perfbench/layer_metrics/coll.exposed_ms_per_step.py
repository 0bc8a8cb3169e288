"""Device time a training step spends in collectives with no other
operation running on the same chip, averaged over chips and steps."""
from perfbench import tracered


def read(run):
    doc, steps = run["ctx"].trace_doc, run["facts"].get("traced_steps")
    if doc is None or not steps:
        return None
    return tracered.exposed_collective_s(doc) * 1e3 / steps
