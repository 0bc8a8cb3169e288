"""What the host itself spends on a tick in the five newer backlog cells
(dots3, DeepSeek-V2, Olmo-Hybrid, Ling-3.0-flash, Falcon-H1), where it stands
against ``serve_tokens_per_s``: the ``pt:step/admit``, ``chunks``, ``grow``,
``build`` and ``dispatch`` spans and the drains that did not have to wait
(``waited=0``), summed over the traced stretch, over the ticks dispatched in
it."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.host_ms_per_tick(pt.doc_of(run))
