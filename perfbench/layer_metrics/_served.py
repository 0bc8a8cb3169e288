"""What the folded readers of the five newer backlog cells share
(``serve-dots3-longdoc-backlog``, ``serve-dsv2-docqa-backlog``,
``serve-olmo-hybrid-gen-backlog``, ``serve-ling3-longgen-backlog``,
``serve-falcon-h1-gen-backlog``): which family's trace helper reads this
run's tick. One entry a quantity stands in ``BENCHMARK.json`` where each cell
brought a copy (``dots3.*``, ``dsv2.*``, ``olmoh.*`` and the ``.longdoc``,
``.dsv2``, ``.olmoh`` suffixes, retired at PR 48; ``ling.*`` and
``kda.prep_ms_per_tick``, retired at PR 53; ``fh1.*``, ``ssd.*``, ``gdn.*``
and ``kda.step_*``, retired at PR 56); a folded reader returns in each cell
the number that cell's copy returned.

A served family's helper is a file ``_<family>_trace.py`` beside this one
that hands out ``parts_ms(run)``, ``read_part(run, part)``,
``tick_shape(run)``, ``tick_needs(run)`` (the mean tick's shape with the
bytes it must move and the operations it must do, by the family's own
yardstick) and, where it has them, ``least_ms(run, part)`` (the least
milliseconds the mean tick needs in one part, by the same yardstick) and
``experts_bytes(run)`` (where its tick holds experts). This file names none
of them: it lists the directory, so a new family brings its helper, written
in this form from the start, and edits nothing here. The helper is found
from the run and not from a cell's or a family's name: each looks for one
operation under its own mechanism's scope before it cuts the trace and gives
``None`` for a tick that does not name it (five mechanisms today, five
helpers), so at most one answers, a toy family's tick is read like its
model's, and a traced run cuts its trace for its own helper alone; the
answer is kept with the trace. A part that several families' ticks have
carries one name in all their helpers (``dense``, ``head_sample``,
``unscoped``, ``scatter``, ``route``, ``experts``, ``shared``,
``mla_decode``, ``attn``, and a recurrent state's ``state_step``,
``state_chunk``, ``state_prep`` whatever its rule): a shared reader asks for
it by that name and names no family. Ouro's ``loop.*`` readers stay as they
are: that cell reports no ``serve_tokens_per_s``, and a per-layer entry moves
one end-to-end metric.
"""
from __future__ import annotations

import os
from typing import List, Optional, Tuple

from perfbench import loader

HERE = os.path.dirname(os.path.abspath(__file__))


def helpers() -> List[str]:
    """The served families' trace helpers: every ``_*_trace.py`` here that
    hands out ``tick_needs`` (``_kda_trace`` and ``_moe_trace`` read a
    training step, ``_loop_trace`` a tick that moves ``itl_p95_ms``)."""
    names = sorted(f[:-3] for f in os.listdir(HERE)
                   if f.startswith("_") and f.endswith("_trace.py"))
    return [n for n in names if hasattr(
        loader.load_module("layer_metrics", n), "tick_needs")]


def trace_of(run):
    """The trace helper whose parts this run's traced tick has; ``None``
    without a trace or for a tick none of them reads. Asked once a trace."""
    pt = loader.load_module("layer_metrics", "_program_trace")
    doc = pt.doc_of(run)
    if doc is None:
        return None

    def find():
        for name in helpers():
            tr = loader.load_module("layer_metrics", name)
            if tr.parts_ms(run) is not None:
                return tr
        return None

    tr = pt._once(doc, "served helper", find)
    if tr is not None:
        note = (f"the tick is {tr.__name__.rsplit('.', 1)[-1]}'s; the trace "
                f"was cut for: {', '.join(pt.cuts_of(doc))}")
        if note not in run.setdefault("notes", []):
            run["notes"].append(note)
    return tr


def read_part(run, part: str) -> Optional[float]:
    """Device milliseconds a tick in ``part``, as the cell's own helper
    splits its tick."""
    tr = trace_of(run)
    return None if tr is None else tr.read_part(run, part)


def tick_needs(run) -> Optional[Tuple[dict, float, float]]:
    """``(tick_shape, bytes, operations)`` of the run's mean tick."""
    tr = trace_of(run)
    return None if tr is None else tr.tick_needs(run)


def roofline_pct(run, part: str) -> Optional[float]:
    """``part``'s share of its roofline: the least milliseconds the run's
    mean tick needs there, as the cell's own helper counts them
    (``least_ms``), over the device milliseconds it took. ``None`` where the
    helper hands out no floor for the part, and never 0."""
    tr = trace_of(run)
    if tr is None or not hasattr(tr, "least_ms"):
        return None
    least, ms = tr.least_ms(run, part), tr.read_part(run, part)
    return None if least is None or not ms else 100.0 * least / ms


def experts_needs(run) -> Optional[Tuple[dict, float, float]]:
    """``(tick_shape, bytes of the held experts' matrices that were given a
    row, the experts' device milliseconds a tick)``; ``None`` for a tick
    that holds no experts."""
    tr = trace_of(run)
    if tr is None or not hasattr(tr, "experts_bytes"):
        return None
    shape, moved = tr.tick_shape(run), tr.experts_bytes(run)
    ms = tr.read_part(run, "experts")
    return None if moved is None or not ms else (shape, moved, ms)
