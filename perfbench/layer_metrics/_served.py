"""What the folded readers of the four newer backlog cells share
(``serve-dots3-longdoc-backlog``, ``serve-dsv2-docqa-backlog``,
``serve-olmo-hybrid-gen-backlog``, ``serve-ling3-longgen-backlog``): which
family's trace helper reads this run's tick. One entry a quantity stands in
``BENCHMARK.json`` where each cell brought a copy (``dots3.*``, ``dsv2.*``,
``olmoh.*`` and the ``.longdoc``, ``.dsv2``, ``.olmoh`` suffixes, retired at
PR 48; ``ling.*`` and ``kda.prep_ms_per_tick``, retired at PR 53); a folded
reader returns in each cell the number that cell's copy returned.

A served family's helper is a file ``_<family>_trace.py`` beside this one
that hands out ``parts_ms(run)``, ``read_part(run, part)``,
``tick_shape(run)``, ``tick_needs(run)`` (the mean tick's shape with the
bytes it must move and the operations it must do, by the family's own
yardstick) and, where its tick holds experts, ``experts_bytes(run)``. This
file names none of them: it lists the directory, so a new family brings its
helper and edits nothing here. The helper is found from the run and not
from a cell's or a family's name: each gives ``None`` for a tick that does
not name its own mechanism (``blk/attn/mla``; ``blk/attn/mla_chunk`` or
``_decode``; ``blk/gdn/step``; ``blk/kda/step``), so at most one answers and
a toy family's tick is read like its model's. A part that two families' ticks
both have carries one name in both helpers (``dense``, ``head_sample``,
``unscoped``, ``scatter``, ``route``, ``experts``, ``shared``,
``mla_decode``, ``gdn_prep``): a shared reader asks for it by that name and
names no family. Ouro's ``loop.*`` readers stay as they are: that cell
reports no ``serve_tokens_per_s``, and a per-layer entry moves one
end-to-end metric.
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
    without a trace or for a tick none of them reads."""
    for name in helpers():
        tr = loader.load_module("layer_metrics", name)
        if tr.parts_ms(run) is not None:
            return tr
    return None


def read_part(run, part: str) -> Optional[float]:
    """Device milliseconds a tick in ``part``, as the cell's own helper
    splits its tick."""
    tr = trace_of(run)
    return None if tr is None else tr.read_part(run, part)


def tick_needs(run) -> Optional[Tuple[dict, float, float]]:
    """``(tick_shape, bytes, operations)`` of the run's mean tick."""
    tr = trace_of(run)
    return None if tr is None else tr.tick_needs(run)


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
