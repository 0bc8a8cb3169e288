"""What the host itself spends on a tick, where it stands against
``serve_tokens_per_s``: the ``pt:step/admit``, ``chunks``, ``grow``, ``build``
and ``dispatch`` spans and the drains that did not have to wait, summed over
the traced stretch, over the ticks dispatched in it
(``_program_trace.host_ms_per_tick``)."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.host_ms_per_tick(pt.doc_of(run))
