"""A looped model's serving tick by part, by the scope names the program
gives its operations (``models/gpt.py``: the ``blk/*`` and ``tick/*``
vocabulary inside the loop, ``loop/exit`` around each step's final norm,
the gate and the choice of the step the head reads). ``_program_trace``
knows no ``loop/`` and counts the scatter apart from the attention; this
file has its own label function over ``_program_trace.parts_ms`` and leaves
that file alone. A program that names no ``loop/exit`` (one that serves no
looped model: the parent of the PR that brought it) gives ``None`` and
raises nothing.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from perfbench import loader, yardstick

_SCOPE = re.compile(r"\b(blk|tick|loop)/([a-z_]+)")
ORDER = ("attn", "dense", "exit", "head_sample", "unscoped")
_PART = {"blk/kv_scatter": "attn", "blk/attn": "attn", "blk/qkv": "dense",
         "blk/attn_out": "dense", "blk/ffn": "dense", "loop/exit": "exit",
         "tick/embed": "head_sample", "tick/head": "head_sample",
         "tick/sample": "head_sample"}


def loop_part(ev: dict) -> str:
    """The innermost of the program's names on an operation's scope path."""
    found = _SCOPE.findall(ev.get("scope", ""))
    return _PART.get("/".join(found[-1]), "unscoped") if found \
        else "unscoped"


def parts_ms(run) -> Optional[Dict[str, float]]:
    """Device milliseconds a tick by part, mean over the traced runs of
    the tick program; ``None`` unless some operation ran under
    ``loop/exit``."""
    pt = loader.load_module("layer_metrics", "_program_trace")
    doc = pt.doc_of(run)
    if doc is None:
        return None

    def compute():
        parts = pt.parts_ms(doc, "tick", loop_part, ORDER)
        if not parts or not parts.get("exit"):
            return None
        n = parts.pop("n_runs")
        return {k: v / n for k, v in parts.items()}

    parts = pt._once(doc, "loop parts", compute)
    if parts is not None:
        pt.say_parts(run, "looped tick's parts a tick", parts)
    return parts


def read_part(run, part: str) -> Optional[float]:
    parts = parts_ms(run)
    return parts.get(part) if parts else None


def tick_shape(run) -> Optional[dict]:
    """What the window's mean tick held, for the yardstick: the tick's
    median device time, the tokens in flight, the rows sampled and the
    cache positions the live requests held. ``None`` where the
    configuration states no loop or no tick was traced."""
    c, f = run["ctx"].config, run["facts"]
    if "total_ut_steps" not in c or parts_ms(run) is None:
        return None
    ms = loader.load_module("layer_metrics", "_tick").device_ms_p50(run)
    if not ms:
        return None
    e = c["engine"]
    rows = f["decode_rows_per_tick"]
    live = f["live_kv_share"] * e["num_slots"] * e["pages_per_slot"] \
        * e["page_size"]
    return {"ms": ms, "live": live, "sampled": rows,
            "tokens": rows + f["prefill_rows_per_tick"] * f["prefill_chunk"],
            "peak": yardstick.chip_peak(run["ctx"].devices[0].device_kind)}
