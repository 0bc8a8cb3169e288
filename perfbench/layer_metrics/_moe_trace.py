"""The expert layer's parts of a traced training step, by the ``moe/``
scope names the program gives them (``distributed/moe.py``: ``moe/route``,
``moe/dispatch``, ``moe/experts``, ``moe/combine`` inside ``blk/ffn``).
``_program_trace`` knows ``blk|tick|fwd|opt`` and counts all of these as
``blk/``; this file has its own pattern and leaves that one alone. The
``moe.*_ms_per_step`` readers are a few lines each on top of it. A program
that names no ``moe/`` part gives ``None`` and raises nothing.

The grouped matmuls themselves carry no scope name: XLA:TPU rewrites
``jax.lax.ragged_dot`` into its own kernel, a custom call whose ``op_name``
is ``ragged-dot-none`` whatever scope it was traced under (seen on the v5e,
PR 26: the kernels' 0.5 s a step read as ``train.unscoped_ms_per_step``).
They are found by their instruction's name, ``ragged-dot...``, and are the
largest part of ``experts``.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from perfbench import loader, tracered

#: XLA:TPU's grouped-matmul kernel, by its instruction's name
RAGGED_DOT = "ragged-dot"
_MOE = re.compile(r"\bmoe/([a-z_]+)")
_PART = {"route": "route", "dispatch": "dispatch_combine",
         "combine": "dispatch_combine", "experts": "experts"}
ORDER = ("experts", "dispatch_combine", "route", "outside")


def moe_part(ev: dict) -> str:
    """``experts`` for a grouped-matmul kernel; else the innermost
    ``moe/`` name on an operation's scope path, forward or inside
    ``transpose(jvp(...))``; ``outside`` under none."""
    if tracered.short_name(ev).startswith(RAGGED_DOT):
        return "experts"
    found = _MOE.findall(ev.get("scope", ""))
    return _PART.get(found[-1], "outside") if found else "outside"


def moe_parts_ms(run) -> Optional[Dict[str, float]]:
    """Device milliseconds a step by part, mean over chips and traced
    steps: forward, recomputed forward and backward together."""
    pt = loader.load_module("layer_metrics", "_program_trace")
    doc, steps = pt.doc_of(run), run["facts"].get("traced_steps")
    if doc is None or not steps:
        return None

    def compute():
        parts = pt.parts_ms(doc, "step", moe_part, ORDER)
        if not parts or not any(parts.get(p) for p in ORDER[:3]):
            return None
        parts.pop("n_runs")
        return {k: v / steps for k, v in parts.items()}

    parts = pt._once(doc, f"moe parts / {steps}", compute)
    if parts is not None:
        pt.say_parts(run, "expert layer's parts a step", parts)
    return parts


def read_part(run, part: str) -> Optional[float]:
    parts = moe_parts_ms(run)
    return None if parts is None else parts[part]
