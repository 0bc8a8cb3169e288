"""Host time between two of Laguna's ticks' dispatches that the device does
not cover (``served.host_ms_per_tick``'s reader): six layers are a short tick,
so the cut in depth raises the host's share."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.host_ms_per_tick(pt.doc_of(run))
