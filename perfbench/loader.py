"""Finds what belongs to one configuration, traffic mix or metric by its name.

Nothing is registered anywhere: ``configs/<name>.json`` names a ``family``
found as ``families/<family>.py``, ``traffic/<name>.json`` names a
``generator`` found as ``generators/<generator>.py``, a per-layer metric is
``layer_metrics/<name>.py`` and a family's check is ``checks/<family>.py``.
A family says what its model is and what its sizes must satisfy: ``build``,
``limits``, ``check_widths(config)`` and what else its loop asks, and
``run``, that loop bound to them. The loops, the window and the reductions
are ``serve_loop.py``'s and ``train_loop.py``'s, once.
A later PR adds files and ``BENCHMARK.json`` entries and edits nothing here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "perfbench")


class NotFound(LookupError):
    """A name in BENCHMARK.json or a data file with no file behind it."""


def root_file(*parts: str) -> str:
    return os.path.join(ROOT, *parts)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """The module ``perfbench/<kind>/<name>.py``. Names may hold dots
    (``tick.device_ms_p50.chat``), so the file is loaded by path."""
    directory = os.path.join(HERE, kind)
    path = os.path.join(directory, name + ".py")
    if not os.path.isfile(path):
        raise NotFound(f"no {kind} named {name!r}: looked for "
                       f"{name}.py in {directory}")
    mod_name = f"perfbench.{kind}.{name.replace('.', '_').replace('-', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return mod


def load_data(kind: str, name: str) -> dict:
    """The data file ``perfbench/<kind>/<name>.json``."""
    directory = os.path.join(HERE, kind)
    path = os.path.join(directory, name + ".json")
    if not os.path.isfile(path):
        raise NotFound(f"no {kind} named {name!r}: looked for "
                       f"{name}.json in {directory}")
    return load_json(path)


def load_cell(workload: str) -> dict:
    """BENCHMARK.json's entries for one cell: the cell, its configuration
    (entry and file), its traffic file and the metrics it reports."""
    bench = load_json(root_file("BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise NotFound(f"no workload named {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "config_entry": entry,
            "config": load_json(root_file(entry["file"])),
            "traffic": load_data("traffic", cell["traffic"]),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}
