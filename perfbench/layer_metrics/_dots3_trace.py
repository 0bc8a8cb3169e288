"""The serving tick of a latent-attention model by part, by the scope names
the program gives its operations (``models/dots3.py``: ``blk/qkv``,
``blk/latent_scatter``, ``blk/index``, ``blk/select``, ``blk/attn/mla``,
``blk/attn/swa``, ``blk/attn_out``, ``blk/ffn``; ``distributed/moe.py``:
``moe/route``, ``moe/dispatch``, ``moe/experts``, ``moe/combine``,
``moe/shared`` inside ``blk/ffn``; ``tick/embed``, ``tick/head``,
``tick/sample``). ``_program_trace`` knows none of the new names and
``_moe_trace`` reads a training step; this file has its own label function
over ``_program_trace.parts_ms`` and leaves both alone. The grouped
matmuls' Pallas calls are found by their instruction's name (``moe_gmm``),
as ``_moe_trace`` finds XLA's. Dispatch and combine (a gather and a
scatter-add of the held rows) are counted with the experts.

A program that names no ``blk/attn/mla`` (one that serves no such model:
the parent of the PR that brought it) gives ``None`` and raises nothing, and
before the trace is cut (``_program_trace.names_scope``).
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from perfbench import loader, tracered, yardstick, yardstick_mla

_PART = {"blk/attn/mla": "mla", "blk/attn/swa": "swa",
         "blk/latent_scatter": "scatter", "blk/index": "index",
         "blk/select": "select", "blk/qkv": "dense", "blk/attn_out": "dense",
         "blk/ffn": "dense", "moe/route": "route", "moe/dispatch": "experts",
         "moe/experts": "experts", "moe/combine": "experts",
         "moe/shared": "shared", "tick/embed": "head_sample",
         "tick/head": "head_sample", "tick/sample": "head_sample"}
_SCOPE = re.compile(r"\b(" + "|".join(
    re.escape(n) for n in sorted(_PART, key=len, reverse=True)) + r")\b")
ORDER = ("experts", "mla", "swa", "index", "select", "scatter", "route",
         "shared", "dense", "head_sample", "unscoped")
GROUPED = ("moe_gmm", "ragged-dot")
#: the tick's own mechanism: no operation under it, not this helper's tick
MECHANISM = ("blk/attn/mla",)


def part(ev: dict) -> str:
    """The innermost of the program's names on an operation's scope path."""
    if tracered.short_name(ev).startswith(GROUPED):
        return "experts"
    found = _SCOPE.findall(ev.get("scope", ""))
    return _PART[found[-1]] if found else "unscoped"


def parts_ms(run) -> Optional[Dict[str, float]]:
    """Device milliseconds a tick by part, mean over the traced runs of
    the tick program; ``None`` unless some operation ran under
    ``blk/attn/mla``."""
    pt = loader.load_module("layer_metrics", "_program_trace")
    doc = pt.doc_of(run)
    if doc is None:
        return None

    def compute():
        if not pt.names_scope(doc, _SCOPE, MECHANISM):
            return None
        parts = pt.parts_ms(doc, "tick", part, ORDER)
        if not parts or not parts.get("mla"):
            return None
        n = parts.pop("n_runs")
        return {k: v / n for k, v in parts.items()}

    parts = pt._once(doc, "dots3 parts", compute)
    if parts is not None:
        pt.say_parts(run, "latent-attention tick's parts a tick", parts)
    return parts


def read_part(run, name: str) -> Optional[float]:
    parts = parts_ms(run)
    if parts is None:
        return None
    if name == "unscoped":       # what no name covers, operation or gap
        return parts.get("unscoped", 0.0) + parts.get("in no operation", 0.0)
    return parts.get(name, 0.0)


def tick_shape(run) -> Optional[dict]:
    """What the window's mean tick held, for ``yardstick_mla``: the tick's
    median device time, its decode rows, chunk rows and chunk width, the
    mean positions behind a live row, and what the ticks said of their
    experts. ``None`` where the configuration states no indexer or no tick
    was traced."""
    c, f = run["ctx"].config, run["facts"]
    if "index_topk" not in c or parts_ms(run) is None:
        return None
    ms = loader.load_module("layer_metrics", "_tick").device_ms_p50(run)
    rows = f["decode_rows_per_tick"] + f["prefill_rows_per_tick"]
    if not ms or not rows:
        return None
    e = c["engine"]
    live = f["live_kv_share"] * e["num_slots"] * e["pages_per_slot"] \
        * e["page_size"]
    return {"ms": ms, "decode": f["decode_rows_per_tick"],
            "chunks": f["prefill_rows_per_tick"],
            "chunk": f["prefill_chunk"], "context": live / rows,
            "sampled": f["decode_rows_per_tick"],
            "touched": f.get("tick_experts_touched_share", 0.0),
            "expert_rows": f.get("tick_expert_rows", 0.0),
            "peak": yardstick.chip_peak(run["ctx"].devices[0].device_kind)}


def tick_needs(run) -> Optional[Tuple[dict, float, float]]:
    """``(tick_shape, bytes the mean tick must move, operations it must
    do)`` by ``yardstick_mla``: what the ``served.*`` shares of the whole
    tick are taken over (``_served`` asks every helper that has this)."""
    s = tick_shape(run)
    if s is None:
        return None
    rows = (run["ctx"].config, s["decode"], s["chunks"], s["chunk"],
            s["context"], s["sampled"])
    return (s, yardstick_mla.tick_bytes(*rows, s["touched"]),
            yardstick_mla.tick_flops(*rows, s["expert_rows"]))


def experts_bytes(run) -> Optional[float]:
    """Bytes of the held experts' matrices that a tick gave a row."""
    s = tick_shape(run)
    return None if s is None else yardstick_mla.experts_bytes(
        run["ctx"].config, s["touched"])


def roofline_pct(run, name: str, ops_bytes) -> Optional[float]:
    """``ops_bytes(config, decode, chunks, chunk, context)``'s least time
    over part ``name``'s device time."""
    s, ms = tick_shape(run), read_part(run, name)
    if s is None or not ms:
        return None
    ops, moved = ops_bytes(run["ctx"].config, s["decode"], s["chunks"],
                           s["chunk"], s["context"])
    return 100.0 * yardstick_mla.least_ms(ops, moved, s["peak"]) / ms
