"""DeepSeek-V2's serving tick by part, by the scope names the program gives
its operations (``models/deepseek_v2.py``: ``blk/qkv``,
``blk/latent_scatter``, ``blk/attn/mla_chunk``, ``blk/attn/mla_decode``,
``blk/attn_out``, ``blk/ffn``; ``distributed/moe.py``: ``moe/route``,
``moe/dispatch``, ``moe/experts``, ``moe/combine``, ``moe/shared`` inside
``blk/ffn``; ``tick/embed``, ``tick/head``, ``tick/sample``).
``_dots3_trace`` reads a tick that names ``blk/attn/mla`` and its table
knows neither of this tick's two attention scopes, so this file has its own
label function over ``_program_trace.parts_ms`` and takes from
``_dots3_trace`` how the grouped matmuls' kernels are found (by their
instruction's name) and from ``_tick`` the tick's device time; both are
imported, neither is edited.

A program that names no ``blk/attn/mla_chunk`` or ``blk/attn/mla_decode``
(one that serves no such model: the parent of the PR that brought it) gives
``None`` and raises nothing, and before the trace is cut
(``_program_trace.names_scope``).
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from perfbench import loader, tracered, yardstick, \
    yardstick_mla_dense

_PART = {"blk/attn/mla_chunk": "mla_chunk",
         "blk/attn/mla_decode": "mla_decode",
         "blk/latent_scatter": "scatter", "blk/qkv": "dense",
         "blk/attn_out": "dense", "blk/ffn": "dense", "moe/route": "route",
         "moe/dispatch": "experts", "moe/experts": "experts",
         "moe/combine": "experts", "moe/shared": "shared",
         "tick/embed": "head_sample", "tick/head": "head_sample",
         "tick/sample": "head_sample"}
_SCOPE = re.compile(r"\b(" + "|".join(
    re.escape(n) for n in sorted(_PART, key=len, reverse=True)) + r")\b")
ORDER = ("experts", "mla_chunk", "mla_decode", "scatter", "route", "shared",
         "dense", "head_sample", "unscoped")
#: the tick's own mechanism: no operation under it, not this helper's tick
MECHANISM = ("blk/attn/mla_chunk", "blk/attn/mla_decode")


def _helper(name: str):
    return loader.load_module("layer_metrics", name)


GROUPED = _helper("_dots3_trace").GROUPED   # once: ``part`` runs an operation


def part(ev: dict) -> str:
    """The innermost of the program's names on an operation's scope path."""
    if tracered.short_name(ev).startswith(GROUPED):
        return "experts"
    found = _SCOPE.findall(ev.get("scope", ""))
    return _PART[found[-1]] if found else "unscoped"


def parts_ms(run) -> Optional[Dict[str, float]]:
    """Device milliseconds a tick by part, mean over the traced runs of the
    tick program; ``None`` unless some operation ran under one of the two
    dense-attention scopes."""
    pt = _helper("_program_trace")
    doc = pt.doc_of(run)
    if doc is None:
        return None

    def compute():
        if not pt.names_scope(doc, _SCOPE, MECHANISM):
            return None
        parts = pt.parts_ms(doc, "tick", part, ORDER)
        if not parts or not (parts.get("mla_chunk")
                             or parts.get("mla_decode")):
            return None
        n = parts.pop("n_runs")
        return {k: v / n for k, v in parts.items()}

    parts = pt._once(doc, "dsv2 parts", compute)
    if parts is not None:
        pt.say_parts(run, "DeepSeek-V2 tick's parts a tick", parts)
    return parts


def read_part(run, name: str) -> Optional[float]:
    parts = parts_ms(run)
    if parts is None:
        return None
    if name == "unscoped":       # what no name covers, operation or gap
        return parts.get("unscoped", 0.0) + parts.get("in no operation", 0.0)
    return parts.get(name, 0.0)


def tick_shape(run) -> Optional[dict]:
    """What the run's mean tick held, for ``yardstick_mla_dense``: the
    tick's median device time, its tokens and sampled rows, the ``(pairs,
    keys)`` of one layer's two attention calls as the ticks counted them,
    and what they said of their experts. ``None`` where the ticks counted
    no pairs or no tick was traced."""
    f = run["facts"]
    if "tick_chunk_pairs" not in f or parts_ms(run) is None:
        return None
    ms = _helper("_tick").device_ms_p50(run)
    if not ms:
        return None
    return {"ms": ms,
            "tokens": f["decode_rows_per_tick"]
            + f["prefill_rows_per_tick"] * f["prefill_chunk"],
            "sampled": f["decode_rows_per_tick"],
            "decode": (f["tick_decode_pairs"], f["tick_decode_keys"]),
            "chunk": (f["tick_chunk_pairs"], f["tick_chunk_keys"]),
            "touched": f.get("tick_experts_touched_share", 0.0),
            "expert_rows": f.get("tick_expert_rows", 0.0),
            "peak": yardstick.chip_peak(run["ctx"].devices[0].device_kind)}


def tick_needs(run) -> Optional[Tuple[dict, float, float]]:
    """``(tick_shape, bytes the mean tick must move, operations it must
    do)`` by ``yardstick_mla_dense``: what the ``served.*`` shares of the
    whole tick are taken over (``_served`` asks every helper that has
    this)."""
    s = tick_shape(run)
    if s is None:
        return None
    rows = (run["ctx"].config, s["tokens"], (s["decode"], s["chunk"]),
            s["sampled"])
    return (s, yardstick_mla_dense.tick_bytes(*rows, s["touched"]),
            yardstick_mla_dense.tick_flops(*rows, s["expert_rows"]))


def experts_bytes(run) -> Optional[float]:
    """Bytes of the held experts' matrices that a tick gave a row."""
    s = tick_shape(run)
    return None if s is None else yardstick_mla_dense.experts_bytes(
        run["ctx"].config, s["touched"])
