"""Olmo-Hybrid's serving tick by part, by the scope names the program gives
its operations (``models/olmo_hybrid.py``: ``blk/gdn/proj``,
``blk/gdn/prep``, ``blk/gdn/step``, ``blk/gdn/chunk``, ``blk/gdn/out``,
``blk/state_io`` in a linear layer; ``blk/qkv``, ``blk/kv_scatter``,
``blk/attn``, ``blk/attn_out`` in a full one; ``blk/ffn``; ``tick/embed``,
``tick/head``, ``tick/sample``). Its own label function over
``_program_trace.parts_ms``; the tick's device time is ``_tick``'s. Both are
imported, neither is edited. Seven parts: ``dense`` (every matrix product
and norm of both kinds of layer: ``blk/gdn/proj``, ``blk/gdn/out``,
``blk/qkv``, ``blk/kv_scatter``, ``blk/attn_out``, ``blk/ffn``),
``gdn_step``, ``gdn_chunk``, ``gdn_prep`` (``blk/gdn/prep`` and
``blk/state_io``: what lies between a linear layer's projections and its
delta rule), ``attn``, ``head_sample`` and ``unscoped``.

A recurrent state's three passes carry one name in every served family's
helper, whatever the rule (``SHARED``: ``state_step``, ``state_chunk``,
``state_prep``; ``attn`` is Falcon-H1's word too): a ``state.*`` or
``attn.full_*`` reader asks ``_served`` for the part by that name and names
no family. The cut's own labels stay the scopes' words, which
``benchmarks/tick_kinds.py`` and ``benchmarks/kda_proj_ops.py`` ask
``part`` and ``ORDER`` for. ``least_ms(run, part)`` is the part's floor by
``yardstick_gdn``, which ``_served.roofline_pct`` divides by the part's time.

A program that names no ``blk/gdn/step`` (one that serves no such model: the
parent of the PR that brought it) gives ``None`` and raises nothing, and
before the trace is cut (``_program_trace.names_scope``).
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from perfbench import loader, yardstick, yardstick_gdn

_PART = {"blk/gdn/proj": "dense", "blk/gdn/prep": "gdn_prep",
         "blk/gdn/step": "gdn_step", "blk/gdn/chunk": "gdn_chunk",
         "blk/gdn/out": "dense", "blk/state_io": "gdn_prep",
         "blk/attn": "attn", "blk/kv_scatter": "dense", "blk/qkv": "dense",
         "blk/attn_out": "dense", "blk/ffn": "dense",
         "tick/embed": "head_sample", "tick/head": "head_sample",
         "tick/sample": "head_sample"}
_SCOPE = re.compile(r"\b(" + "|".join(
    re.escape(n) for n in sorted(_PART, key=len, reverse=True)) + r")\b")
ORDER = ("dense", "gdn_step", "attn", "gdn_prep", "gdn_chunk",
         "head_sample", "unscoped")
#: the tick's own mechanism: no operation under it, not this helper's tick
MECHANISM = ("blk/gdn/step",)
#: the names a shared reader asks a recurrent state's passes by
SHARED = {"state_step": "gdn_step", "state_chunk": "gdn_chunk",
          "state_prep": "gdn_prep"}
#: part -> ``(config, tick_shape) -> least milliseconds``: the slower of doing
#: the part's operations and moving its bytes
_FLOOR = {
    "state_step": lambda c, s: yardstick_gdn.least_ms(
        yardstick_gdn.step_flops(c, s["live"]),
        yardstick_gdn.step_bytes(c, s["live"]), s["peak"]),
    "state_chunk": lambda c, s: yardstick_gdn.least_ms(
        yardstick_gdn.chunk_flops(c, s["chunk"]),
        yardstick_gdn.chunk_bytes(c, s["chunk"], s["chunk_rows"]), s["peak"]),
    "attn": lambda c, s: yardstick_gdn.least_ms(
        yardstick_gdn.attention_flops(c, s["decode_keys"] + s["chunk_pairs"]),
        yardstick_gdn.attention_bytes(c, s["decode_keys"] + s["chunk_keys"]),
        s["peak"]),
}


def _helper(name: str):
    return loader.load_module("layer_metrics", name)


def part(ev: dict) -> str:
    """The innermost of the program's names on an operation's scope path."""
    found = _SCOPE.findall(ev.get("scope", ""))
    return _PART[found[-1]] if found else "unscoped"


def parts_ms(run) -> Optional[Dict[str, float]]:
    """Device milliseconds a tick by part, mean over the traced runs of the
    tick program; ``None`` unless some operation ran under
    ``blk/gdn/step``."""
    pt = _helper("_program_trace")
    doc = pt.doc_of(run)
    if doc is None:
        return None

    def compute():
        if not pt.names_scope(doc, _SCOPE, MECHANISM):
            return None
        parts = pt.parts_ms(doc, "tick", part, ORDER)
        if not parts or not parts.get("gdn_step"):
            return None
        n = parts.pop("n_runs")
        return {k: v / n for k, v in parts.items()}

    parts = pt._once(doc, "olmoh parts", compute)
    if parts is not None:
        pt.say_parts(run, "Olmo-Hybrid tick's parts a tick", parts)
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
    """What the run's mean tick held, for ``yardstick_gdn``: the tick's
    median device time and its rows, tokens, keys and pairs as the ticks
    counted them. ``None`` where the ticks counted no state rows or no tick
    was traced."""
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
            "decode_keys": f["tick_decode_keys"],
            "chunk_keys": f["tick_chunk_keys"],
            "chunk_pairs": f["tick_chunk_pairs"],
            "peak": yardstick.chip_peak(run["ctx"].devices[0].device_kind)}


def tick_needs(run) -> Optional[Tuple[dict, float, float]]:
    """``(tick_shape, bytes the mean tick must move, operations it must
    do)`` by ``yardstick_gdn``: what the ``served.*`` shares of the whole
    tick are taken over (``_served`` asks every helper that has this). No
    ``experts_bytes``: this tick holds no experts."""
    s = tick_shape(run)
    if s is None:
        return None
    c = run["ctx"].config
    return s, yardstick_gdn.tick_bytes(c, s), yardstick_gdn.tick_flops(c, s)


def least_ms(run, part: str) -> Optional[float]:
    """The least device milliseconds the run's mean tick needs in ``part``
    by ``yardstick_gdn`` (the slower of moving its bytes and doing its
    operations); ``None`` for a part with no floor here."""
    s, floor = tick_shape(run), _FLOOR.get(part)
    if s is None or floor is None:
        return None
    return floor(run["ctx"].config, s)
