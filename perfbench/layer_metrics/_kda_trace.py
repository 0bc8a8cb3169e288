"""The linear-attention layer's parts of a traced training step, and the
shared expert's, by the scope names the program gives them
(``models/solar_open2.py``: ``blk/kda/proj``, ``blk/kda/scan``,
``blk/kda/out``; ``distributed/moe.py``: ``moe/shared``) and by the scan
kernels' own names (``ops/kda.py``: ``kda_fwd``, ``kda_bwd_states``,
``kda_bwd_grads``). ``_program_trace`` counts all of these as ``blk/`` and
``_moe_trace`` knows no ``shared``; this file has its own pattern and
leaves both alone. A program that names none of it (the parent of the PR
that brought the layer) gives ``None`` and raises nothing.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from perfbench import loader

#: the scan's Pallas calls, by their instruction's name
KDA_KERNELS = ("kda_fwd", "kda_bwd")
_PART = re.compile(r"\b(blk/kda/proj|blk/kda/out|blk/kda/scan|moe/shared)\b")
ORDER = ("scan", "proj", "out", "shared", "outside")
_LABEL = {"blk/kda/proj": "proj", "blk/kda/out": "out",
          "blk/kda/scan": "scan", "moe/shared": "shared"}


def kda_part(ev: dict) -> str:
    """The innermost of this file's names on an operation's scope path,
    forward or inside ``transpose(jvp(...))``; ``outside`` under none."""
    found = _PART.findall(ev.get("scope", ""))
    return _LABEL[found[-1]] if found else "outside"


def scan_ms(run) -> Optional[float]:
    """Device milliseconds a step in the scan's kernels: forward,
    recomputed forward, the states' sweep and the backward sweep."""
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.kernel_ms(pt.doc_of(run), KDA_KERNELS,
                        run["facts"].get("traced_steps"))


def parts_ms(run) -> Optional[Dict[str, float]]:
    """Device milliseconds a step by part, mean over chips and traced
    steps: forward, recomputed forward and backward together."""
    pt = loader.load_module("layer_metrics", "_program_trace")
    doc, steps = pt.doc_of(run), run["facts"].get("traced_steps")
    if doc is None or not steps:
        return None

    def compute():
        parts = pt.parts_ms(doc, "step", kda_part, ORDER)
        if not parts or not any(parts.get(p) for p in ORDER[:4]):
            return None
        return {k: parts.get(k, 0.0) / steps for k in ORDER[:4]}

    parts = pt._once(doc, f"kda parts / {steps}", compute)
    if parts is not None:
        pt.say_parts(run, "linear-attention layer's parts a step", parts)
    return parts


def read_part(run, part: str) -> Optional[float]:
    parts = parts_ms(run)
    return (parts[part] or None) if parts else None
