"""Laguna's serving tick by part, by the scope names the program gives its
operations (``models/laguna.py``: ``blk/qkv``, ``blk/kv_scatter``,
``blk/attn/full``, ``blk/attn/window``, ``blk/attn_out``; ``blk/ffn`` and
inside it ``distributed/moe.py``'s ``moe/route``, ``moe/dispatch``,
``moe/experts``, ``moe/combine``, ``moe/shared``; ``tick/embed``,
``tick/head``, ``tick/sample``). Its own label function over
``_program_trace.parts_ms``; the tick's device time is ``_tick``'s. Both are
imported, neither is edited. The grouped matmuls' Pallas calls are found by
their instruction's name, as ``_dots3_trace`` finds them. Nine parts:
``dense`` (``blk/qkv``, ``blk/attn_out`` and what of ``blk/ffn`` is outside
the ``moe/`` parts), ``attn`` (the full layers' attention: the name
``attn.full_*``'s readers ask by), ``attn_window``, ``scatter``, ``route``,
``experts`` (dispatch and combine with them), ``shared``, ``head_sample`` and
``unscoped``.

Written in the served form (PERF.md section 7): ``least_ms(run, part)``
for ``attn`` and ``attn_window`` by ``yardstick_laguna``, ``experts_bytes``,
the shared part names, and a look for one operation under its own mechanism's
scope, ``blk/attn/window``, before it cuts the trace. **It hands out
``needs``, not ``tick_needs``**, so ``_served.helpers()`` does not list it:
two accepted tests hold that list closed
(``tests/perfbench/test_pb_fold.py::test_a_sixth_helper_beside_the_five_
fails_nothing`` wants exactly the five and the sixth it writes itself;
``test_pb_program_trace.py::test_a_helper_looks_for_its_mechanism_before_it_
cuts`` maps every listed helper's ``MECHANISM`` through a closed table), and
a file under ``tests/perfbench/`` that the parent has is a ``benchmark`` PR's
to edit. Until that PR folds the cell's ``lag.*`` entries, their readers ask
this module what the shared readers ask ``_served`` (``read_part``,
``needs``, ``roofline_pct``, ``experts_needs``, under ``_served``'s names and
meanings): the fold renames ``needs`` to ``tick_needs``, takes the three
functions below it away and joins lists. A program that names no
``blk/attn/window`` (one that serves no such model: the parent of the PR that
brought it) gives ``None`` and raises nothing.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from perfbench import loader, tracered, yardstick, yardstick_laguna

_PART = {"blk/qkv": "dense", "blk/kv_scatter": "scatter",
         "blk/attn/full": "attn", "blk/attn/window": "attn_window",
         "blk/attn_out": "dense", "blk/ffn": "dense", "moe/route": "route",
         "moe/dispatch": "experts", "moe/experts": "experts",
         "moe/combine": "experts", "moe/shared": "shared",
         "tick/embed": "head_sample", "tick/head": "head_sample",
         "tick/sample": "head_sample"}
_SCOPE = re.compile(r"\b(" + "|".join(
    re.escape(n) for n in sorted(_PART, key=len, reverse=True)) + r")\b")
ORDER = ("experts", "attn", "dense", "attn_window", "route", "shared",
         "scatter", "head_sample", "unscoped")
#: the tick's own mechanism: no operation under it, not this helper's tick
MECHANISM = ("blk/attn/window",)
#: every part here carries the name the shared readers ask by already
SHARED: Dict[str, str] = {}
#: part -> ``(config, tick_shape) -> least milliseconds``
_FLOOR = {
    "attn": lambda c, s: yardstick_laguna.attention_least_ms(
        c, yardstick_laguna.FULL, s),
    "attn_window": lambda c, s: yardstick_laguna.attention_least_ms(
        c, yardstick_laguna.SLIDING, s),
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
    ``blk/attn/window``."""
    pt = _helper("_program_trace")
    doc = pt.doc_of(run)
    if doc is None:
        return None

    def compute():
        if not pt.names_scope(doc, _SCOPE, MECHANISM):
            return None
        parts = pt.parts_ms(doc, "tick", part, ORDER)
        if not parts or not parts.get("attn_window"):
            return None
        n = parts.pop("n_runs")
        return {k: v / n for k, v in parts.items()}

    parts = pt._once(doc, "laguna parts", compute)
    if parts is not None:
        pt.say_parts(run, "Laguna tick's parts a tick", parts)
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
    """What the run's mean tick held, for ``yardstick_laguna``: the tick's
    median device time and its rows, tokens, keys and pairs of both kinds of
    attention and what the ticks said of their experts. ``None`` where the
    ticks counted no windowed keys or no tick was traced."""
    f = run["facts"]
    if "tick_window_decode_keys" not in f or parts_ms(run) is None:
        return None
    ms = _helper("_tick").device_ms_p50(run)
    if not ms:
        return None
    shape = {k: f["tick_" + k] for k in (
        "decode_keys", "chunk_keys", "chunk_pairs", "window_decode_keys",
        "window_chunk_keys", "window_chunk_pairs", "expert_rows")}
    return dict(shape, ms=ms, decode=f["tick_decode_rows"],
                chunk=f["tick_chunk_tokens"],
                sampled=f["decode_rows_per_tick"],
                touched=f["tick_experts_touched_share"],
                peak=yardstick.chip_peak(
                    run["ctx"].devices[0].device_kind))


def needs(run) -> Optional[Tuple[dict, float, float]]:
    """``(tick_shape, bytes the mean tick must move, operations it must
    do)`` by ``yardstick_laguna``: what the shares of the whole tick are
    taken over (a listed helper's ``tick_needs``)."""
    s = tick_shape(run)
    if s is None:
        return None
    c = run["ctx"].config
    return s, yardstick_laguna.tick_bytes(c, s), \
        yardstick_laguna.tick_flops(c, s)


def experts_bytes(run) -> Optional[float]:
    """Bytes of the held experts' matrices that a tick gave a row."""
    s = tick_shape(run)
    return None if s is None else yardstick_laguna.experts_bytes(
        run["ctx"].config, s["touched"])


def least_ms(run, part: str) -> Optional[float]:
    """The least device milliseconds the run's mean tick needs in ``part``
    by ``yardstick_laguna`` (the slower of moving its bytes and doing its
    operations); ``None`` for a part with no floor here."""
    s, floor = tick_shape(run), _FLOOR.get(part)
    if s is None or floor is None:
        return None
    return floor(run["ctx"].config, s)


def roofline_pct(run, part: str) -> Optional[float]:
    """``_served.roofline_pct`` of this helper: ``part``'s floor over the
    device milliseconds it took; never 0."""
    least, ms = least_ms(run, part), read_part(run, part)
    return None if least is None or not ms else 100.0 * least / ms


def experts_needs(run) -> Optional[Tuple[dict, float, float]]:
    """``_served.experts_needs`` of this helper: ``(tick_shape, bytes of the
    held experts' matrices that were given a row, the experts' device
    milliseconds a tick)``."""
    shape, moved = tick_shape(run), experts_bytes(run)
    ms = read_part(run, "experts")
    return None if moved is None or not ms else (shape, moved, ms)
