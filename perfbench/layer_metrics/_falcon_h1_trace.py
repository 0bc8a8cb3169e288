"""Falcon-H1's serving tick by part, by the scope names the program gives its
operations (``models/falcon_h1.py``: ``blk/ssd/proj``, ``blk/ssd/prep``,
``blk/ssd/step``, ``blk/ssd/chunk``, ``blk/ssd/out``; ``blk/qkv``,
``blk/kv_scatter``, ``blk/attn/full``, ``blk/attn_out``; ``blk/ffn``;
``tick/embed``, ``tick/head``, ``tick/sample``). Its own label function over
``_program_trace.parts_ms``; the tick's device time is ``_tick``'s. Both are
imported, neither is edited. Seven parts: ``dense`` (every matrix product and
norm: ``blk/ssd/proj``, ``blk/ssd/out``, ``blk/qkv``, ``blk/kv_scatter``,
``blk/attn_out``, ``blk/ffn``), ``ssd_step``, ``ssd_chunk``, ``ssd_prep``,
``attn``, ``head_sample`` and ``unscoped``.

**It hands out ``needs``, not ``tick_needs``**: an accepted test holds
``_served.helpers()`` at the four accepted helpers and an accepted entry's
``workloads`` list is closed to the PR that brought this cell, so the cell
reports under its own names (``fh1.*``, ``ssd.*``) and its readers ask this
helper directly, as Ling's did at PR 49, until a ``benchmark`` PR folds them.
A program that names no ``blk/ssd/step`` (one that serves no such model: the
parent of the PR that brought it) gives ``None`` and raises nothing.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from perfbench import loader, yardstick, yardstick_ssd

_PART = {"blk/ssd/proj": "dense", "blk/ssd/prep": "ssd_prep",
         "blk/ssd/step": "ssd_step", "blk/ssd/chunk": "ssd_chunk",
         "blk/ssd/out": "dense", "blk/attn/full": "attn",
         "blk/kv_scatter": "dense", "blk/qkv": "dense",
         "blk/attn_out": "dense", "blk/ffn": "dense",
         "tick/embed": "head_sample", "tick/head": "head_sample",
         "tick/sample": "head_sample"}
_SCOPE = re.compile(r"\b(" + "|".join(
    re.escape(n) for n in sorted(_PART, key=len, reverse=True)) + r")\b")
ORDER = ("dense", "ssd_step", "attn", "ssd_prep", "ssd_chunk", "head_sample",
         "unscoped")


def _helper(name: str):
    return loader.load_module("layer_metrics", name)


def part(ev: dict) -> str:
    """The innermost of the program's names on an operation's scope path."""
    found = _SCOPE.findall(ev.get("scope", ""))
    return _PART[found[-1]] if found else "unscoped"


def parts_ms(run) -> Optional[Dict[str, float]]:
    """Device milliseconds a tick by part, mean over the traced runs of the
    tick program; ``None`` unless some operation ran under
    ``blk/ssd/step``."""
    pt = _helper("_program_trace")
    doc = pt.doc_of(run)
    if doc is None:
        return None

    def compute():
        parts = pt.parts_ms(doc, "tick", part, ORDER)
        if not parts or not parts.get("ssd_step"):
            return None
        n = parts.pop("n_runs")
        return {k: v / n for k, v in parts.items()}

    parts = pt._once(doc, "falcon-h1 parts", compute)
    if parts is not None:
        pt.say_parts(run, "Falcon-H1 tick's parts a tick", parts)
    return parts


def read_part(run, name: str) -> Optional[float]:
    parts = parts_ms(run)
    if parts is None:
        return None
    if name == "unscoped":       # what no name covers, operation or gap
        return parts.get("unscoped", 0.0) + parts.get("in no operation", 0.0)
    return parts.get(name, 0.0)


def tick_ms(run) -> Optional[float]:
    """The tick program's median device time, of a tick this helper reads."""
    if parts_ms(run) is None:
        return None
    return _helper("_tick").device_ms_p50(run)


def tick_shape(run) -> Optional[dict]:
    """What the run's mean tick held, for ``yardstick_ssd``: the tick's
    median device time and its rows, tokens, keys and pairs as the ticks
    counted them. ``None`` where the ticks counted no state rows or no tick
    was traced."""
    f = run["facts"]
    ms = tick_ms(run)
    if "tick_live_state_rows" not in f or not ms:
        return None
    return {"ms": ms, "live": f["tick_live_state_rows"],
            "chunk": f["tick_chunk_tokens"],
            "chunk_rows": f["prefill_rows_per_tick"],
            "sampled": f["decode_rows_per_tick"],
            "decode_keys": f["tick_decode_keys"],
            "chunk_keys": f["tick_chunk_keys"],
            "chunk_pairs": f["tick_chunk_pairs"],
            "peak": yardstick.chip_peak(run["ctx"].devices[0].device_kind)}


def needs(run) -> Optional[Tuple[dict, float, float]]:
    """``(tick_shape, bytes the mean tick must move, operations it must
    do)`` by ``yardstick_ssd``: what the cell's shares of the whole tick are
    taken over."""
    s = tick_shape(run)
    if s is None:
        return None
    c = run["ctx"].config
    return s, yardstick_ssd.tick_bytes(c, s), yardstick_ssd.tick_flops(c, s)


def roofline_pct(run, name: str, least) -> Optional[float]:
    """``least(config, shape, peak)`` milliseconds over part ``name``'s."""
    s = tick_shape(run)
    ms = read_part(run, name)
    if s is None or not ms:
        return None
    return 100.0 * least(run["ctx"].config, s, s["peak"]) / ms
