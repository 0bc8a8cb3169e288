"""Of the device's idle time in the traced stretch, the share that began
while the host was inside none of the program's ``pt:`` spans: what the
spans inside ``ServingEngine`` do not yet explain. The two open-loop cells
(between ticks of 5.3 ms and, in the looped model's, of 56 ms).
"""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    doc = pt.doc_of(run)
    value = pt.idle_outside_pct(doc)
    if value is not None:
        run.setdefault("notes", []).append(pt.idle_table(doc))
    return value
