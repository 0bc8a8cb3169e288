"""Laguna's check and every one of its controls, on the chip, at the
published widths and at lengths of the cell's traffic:

    chiprun --timeout 3000 -- \
        python3 benchmarks/laguna_controls.py [seed] [memory] [control ...]

Builds and warms the engine as ``perfbench/families/laguna_serve.py``
does, serves a few requests of the cell's sizes until the first three have
finished while the others still decode (the check compares the full and the
windowed layers' pages of slots that still hold their request), then hands
them to ``perfbench/checks/laguna_serve.py`` once as served and once a
control,
and prints each verdict's note: the readings beside their limits. The served
path must come out correct and every control not. Exits non-zero otherwise.
It prints the device's memory in use and at the peak by phase; with
``memory`` after the seed it stops after serving, and with controls' names
it runs the served path and those alone. No CPU mode (the widths do not fit
a test).
"""
from __future__ import annotations

import os
import sys
import time
import types

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import harness, loader  # noqa: E402

CELL, FAMILY = "serve-laguna-mixedlen-backlog", "laguna_serve"
#: (prompt, output) tokens: three that finish (prompts on both sides of a
#: chunk and of the window, and one of the cell's longest: past 512 pages
#: and past YaRN's original 8,192 positions) and three that still decode,
#: one of them as long
SIZES = ((1030, 200), (2794, 160), (16288, 120), (2794, 655), (1030, 901),
         (16288, 1365))
FINISH = 3


def memory(when: str, device) -> None:
    stats = device.memory_stats() or {}
    print(f"memory, {when}: {stats.get('bytes_in_use', 0) / 1e9:.3f} GB in "
          f"use, {stats.get('peak_bytes_in_use', 0) / 1e9:.3f} GB at the "
          "peak", flush=True)


def main(argv) -> int:
    seed = int(argv[1]) if len(argv) > 1 else 2147483693
    harness.enable_compile_cache()
    devices = harness.require_tpu(1)
    cell = loader.load_cell(CELL)
    ctx = harness.Context(cell, seed, 1.0, False, devices)
    family = loader.load_module("families", FAMILY)
    check = loader.load_module("checks", FAMILY)
    _, eng = family.build(ctx)
    memory("engine built (weights drawn, pools made)", devices[0])
    family.warm_up(ctx, eng)        # the tick compiled
    memory("tick compiled and run", devices[0])
    eng.tick_record.watch = lambda rid: True    # every request is checked
    rng = np.random.default_rng(seed)
    requests = [{"prompt": rng.integers(0, ctx.config["vocab_size"], n,
                                        dtype=np.int32),
                 "max_new": m, "due_s": 0.0} for n, m in SIZES]
    t0 = time.perf_counter()
    rids = [eng.submit(r["prompt"], r["max_new"]) for r in requests]
    done = lambda i: len(eng.tokens_so_far(rids[i])) \
        >= requests[i]["max_new"]                           # noqa: E731
    while not all(done(i) for i in range(FINISH)):
        eng.step()
    eng.drain(0)
    finished = [i for i in range(len(rids)) if done(i)]
    print(f"served {len(finished)} of {len(rids)} requests to their end in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    memory("requests served", devices[0])
    if "memory" in argv[2:]:
        return 0
    from paddle_tpu.profiler import registry

    drive = types.SimpleNamespace(
        eng=eng, rid_of=dict(enumerate(rids)), reg=registry(),
        output=lambda i: np.asarray(eng.tokens_so_far(rids[i]), np.int32))
    plan = {"requests": requests}
    wrong = []
    named = [a for a in argv[2:] if a != "memory"]
    for control in [None] + (named or list(check.controls(ctx.config))):
        t0 = time.perf_counter()
        verdict = check.check(ctx, eng.served_weights(), plan, drive,
                              finished, control=control)
        print(f"[{time.perf_counter() - t0:.0f} s] ok={verdict['ok']} "
              f"{verdict['note']}", flush=True)
        if verdict["ok"] != (control is None):
            wrong.append(control)
    memory("checks run", devices[0])
    print("wrong verdicts:", wrong or "none", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
