"""Ling-3.0-flash's serving tick by part, by the scope names the program gives
its operations (``models/ling3.py``: ``blk/kda/proj``, ``blk/kda/prep``,
``blk/kda/step``, ``blk/kda/chunk``, ``blk/kda/out`` in a KDA layer;
``blk/qkv``, ``blk/latent_scatter``, ``blk/mla/decode``, ``blk/mla/chunk``,
``blk/attn_out`` in the MLA layer; ``blk/ffn`` and inside it
``distributed/moe.py``'s ``moe/route``, ``moe/dispatch``, ``moe/experts``,
``moe/combine``, ``moe/shared``; ``tick/embed``, ``tick/head``,
``tick/sample``). Its own label function over ``_program_trace.parts_ms``;
the tick's device time is ``_tick``'s. Both are imported, neither is edited.
The grouped matmuls' Pallas calls are found by their instruction's name, as
``_dots3_trace`` finds them. Eleven parts: ``dense`` (every matrix product
and norm outside the experts: ``blk/kda/proj``, ``blk/kda/out``, ``blk/qkv``,
``blk/attn_out``, what of ``blk/ffn`` is outside the ``moe/`` parts),
``kda_step``, ``kda_chunk``, ``gdn_prep``, ``mla_decode``, ``mla_chunk``,
``scatter``, ``route``, ``experts`` (dispatch and combine with them),
``shared``, ``head_sample`` and ``unscoped``. A part that another served
family's helper also cuts is asked for by one name in every cell, so that
one entry's reader asks ``_served`` for it and names no family:
``mla_decode`` is DeepSeek-V2's word for the decode rows' dense latent
attention, and a recurrent state's three passes are ``state_step``,
``state_chunk`` and ``state_prep`` whatever the rule (``SHARED``;
``gdn_prep`` is ``blk/kda/prep``, ``ops/gdn.gdn_prep_rows``, the pass
Olmo-Hybrid's tick runs under ``blk/gdn/prep``). ``least_ms(run, part)`` is
the part's floor by ``yardstick_ling3``, which ``_served.roofline_pct``
divides by the part's time.

The MLA layer's attention runs under ``blk/mla/...`` and not under the
``blk/attn/mla...`` names of the two latent-attention families, so that at
most one served family's helper answers for a tick. This helper is one of
those ``_served.helpers()`` lists (it hands out ``tick_needs``; ``needs``
until PR 53, when the cell's own ``ling.*`` entries were folded into the
``served.*``, ``moe.tick_*``, ``latent.*``, ``mla.dense_decode`` and
``pool.*`` entries; its step and the pass before it report under ``state.*``
since PR 56). ``ling.mla_decode_roofline_pct``, the decode rows' attention
alone over its own floor, is this cell's entry alone and asks ``_served`` for
it like the others.
A program that names no ``blk/kda/step`` (one that serves no such model: the
parent of the PR that brought it) gives ``None`` and raises nothing, and
before the trace is cut (``_program_trace.names_scope``).
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from perfbench import loader, tracered, yardstick, yardstick_ling3

_PART = {"blk/kda/proj": "dense", "blk/kda/prep": "gdn_prep",
         "blk/kda/step": "kda_step", "blk/kda/chunk": "kda_chunk",
         "blk/kda/out": "dense", "blk/mla/decode": "mla_decode",
         "blk/mla/chunk": "mla_chunk", "blk/latent_scatter": "scatter",
         "blk/qkv": "dense", "blk/attn_out": "dense", "blk/ffn": "dense",
         "moe/route": "route", "moe/dispatch": "experts",
         "moe/experts": "experts", "moe/combine": "experts",
         "moe/shared": "shared", "tick/embed": "head_sample",
         "tick/head": "head_sample", "tick/sample": "head_sample"}
_SCOPE = re.compile(r"\b(" + "|".join(
    re.escape(n) for n in sorted(_PART, key=len, reverse=True)) + r")\b")
ORDER = ("experts", "kda_step", "dense", "mla_decode", "gdn_prep", "route",
         "shared", "scatter", "kda_chunk", "mla_chunk", "head_sample",
         "unscoped")
#: the tick's own mechanism: no operation under it, not this helper's tick
MECHANISM = ("blk/kda/step",)
#: the names a shared reader asks a recurrent state's passes by
SHARED = {"state_step": "kda_step", "state_chunk": "kda_chunk",
          "state_prep": "gdn_prep"}
#: part -> ``(config, tick_shape) -> least milliseconds`` (the window is all
#: decode: the chunk rows' passes have no entry and no floor here)
_FLOOR = {
    "state_step": lambda c, s: yardstick_ling3.least_ms(
        yardstick_ling3.step_flops(c, s["live"]),
        yardstick_ling3.step_bytes(c, s["live"]), s["peak"]),
    "mla_decode": lambda c, s: yardstick_ling3.attention_least_ms(
        c, (s["decode"],), s["peak"]),
}


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
    tick program; ``None`` unless some operation ran under
    ``blk/kda/step``."""
    pt = _helper("_program_trace")
    doc = pt.doc_of(run)
    if doc is None:
        return None

    def compute():
        if not pt.names_scope(doc, _SCOPE, MECHANISM):
            return None
        parts = pt.parts_ms(doc, "tick", part, ORDER)
        if not parts or not parts.get("kda_step"):
            return None
        n = parts.pop("n_runs")
        return {k: v / n for k, v in parts.items()}

    parts = pt._once(doc, "ling3 parts", compute)
    if parts is not None:
        pt.say_parts(run, "Ling-3.0 tick's parts a tick", parts)
    return parts


def read_part(run, name: str) -> Optional[float]:
    parts = parts_ms(run)
    if parts is None:
        return None
    name = SHARED.get(name, name)
    if name == "unscoped":       # what no name covers, operation or gap
        return parts.get("unscoped", 0.0) + parts.get("in no operation", 0.0)
    return parts.get(name, 0.0)


def tick_shape(run) -> Optional[dict]:
    """What the run's mean tick held, for ``yardstick_ling3``: the tick's
    median device time and its rows, tokens, ``(pairs, keys)`` of the MLA
    layer's two calls and what the ticks said of their experts. ``None``
    where the ticks counted no state rows or no tick was traced."""
    f = run["facts"]
    if "tick_live_state_rows" not in f or parts_ms(run) is None:
        return None
    ms = _helper("_tick").device_ms_p50(run)
    if not ms:
        return None
    return {"ms": ms, "live": f["tick_live_state_rows"],
            "chunk": f["tick_chunk_tokens"],
            "chunk_rows": f["prefill_rows_per_tick"],
            "sampled": f["decode_rows_per_tick"],
            "decode": (f["tick_decode_pairs"], f["tick_decode_keys"]),
            "chunk_attn": (f["tick_chunk_pairs"], f["tick_chunk_keys"]),
            "touched": f["tick_experts_touched_share"],
            "expert_rows": f["tick_expert_rows"],
            "peak": yardstick.chip_peak(run["ctx"].devices[0].device_kind)}


def tick_needs(run) -> Optional[Tuple[dict, float, float]]:
    """``(tick_shape, bytes the mean tick must move, operations it must
    do)`` by ``yardstick_ling3``: what the ``served.*`` shares of the whole
    tick are taken over (``_served`` asks every helper that has this)."""
    s = tick_shape(run)
    if s is None:
        return None
    c = run["ctx"].config
    return s, yardstick_ling3.tick_bytes(c, s), \
        yardstick_ling3.tick_flops(c, s)


def experts_bytes(run) -> Optional[float]:
    """Bytes of the held experts' matrices that a tick gave a row."""
    s = tick_shape(run)
    return None if s is None else yardstick_ling3.experts_bytes(
        run["ctx"].config, s["touched"])


def least_ms(run, part: str) -> Optional[float]:
    """The least device milliseconds the run's mean tick needs in ``part``
    by ``yardstick_ling3`` (the slower of moving its bytes and doing its
    operations; the decode rows' attention alone in the lesser of its two
    forms); ``None`` for a part with no floor here."""
    s, floor = tick_shape(run), _FLOOR.get(part)
    if s is None or floor is None:
        return None
    return floor(run["ctx"].config, s)
