"""Device time a training step spends in the Pallas flash-attention
kernels (forward, recomputed forward and the two backward kernels): the
Mosaic custom calls of the traced steps, averaged over chips and steps."""
from perfbench import tracered


def read(run):
    doc, steps = run["ctx"].trace_doc, run["facts"].get("traced_steps")
    if doc is None or not steps:
        return None
    ms = tracered.kernel_s(doc, tracered.is_mosaic_call) * 1e3
    return ms / steps if ms else None
