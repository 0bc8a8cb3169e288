"""A latent-attention cell's check and every one of its controls, on the chip,
at the published widths and at lengths of the cell's traffic:

    chiprun --timeout 3000 -- \
        python3 benchmarks/latent_controls.py dots3|dsv2 [seed] [memory]

Builds and warms the engine as the cell's family in ``perfbench/families/``
does, serves a few requests (``CELLS``: dots3-note-prev prompts of 8-17 k
tokens, DeepSeek-V2 of 4-10 k, every one past YaRN's original 4,096), then
hands them to the family's check in ``perfbench/checks/`` once as served and
once a control, and prints each verdict's note: the readings beside their
limits. The served path must come out correct and every control not. Exits
non-zero otherwise. It prints the device's memory in use and at the peak by phase;
with ``memory`` after the seed it stops after serving. No CPU mode (the
widths do not fit a test).
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

#: the cell, its family and check, (prompt, output) tokens of the requests
CELLS = {
    "dots3": ("serve-dots3-longdoc-backlog", "dots3_serve",
              ((8300, 120), (16600, 200), (12100, 96))),
    "dsv2": ("serve-dsv2-docqa-backlog", "deepseek_v2_serve",
             ((4300, 120), (10300, 200), (6500, 96))),
}


def memory(when: str, device) -> None:
    stats = device.memory_stats() or {}
    print(f"memory, {when}: {stats.get('bytes_in_use', 0) / 1e9:.3f} GB in "
          f"use, {stats.get('peak_bytes_in_use', 0) / 1e9:.3f} GB at the "
          "peak", flush=True)


def main(argv) -> int:
    cell_name, family_name, sizes = CELLS[argv[1]]
    seed = int(argv[2]) if len(argv) > 2 else 2147483693
    harness.enable_compile_cache()
    devices = harness.require_tpu(1)
    cell = loader.load_cell(cell_name)
    ctx = harness.Context(cell, seed, 1.0, False, devices)
    family = loader.load_module("families", family_name)
    check = loader.load_module("checks", family_name)
    _, eng = family.build(ctx)
    memory("engine built (weights drawn, pools made)", devices[0])
    family.warm_up(ctx, eng)        # the tick compiled
    memory("tick compiled and run", devices[0])
    eng.tick_record.watch = lambda rid: True    # every request is checked
    rng = np.random.default_rng(seed)
    requests = [{"prompt": rng.integers(0, ctx.config["vocab_size"], n,
                                        dtype=np.int32),
                 "max_new": m, "due_s": 0.0} for n, m in sizes]
    t0 = time.perf_counter()
    rids = [eng.submit(r["prompt"], r["max_new"]) for r in requests]
    eng.run()
    print(f"served {len(rids)} requests in {time.perf_counter() - t0:.1f} s",
          flush=True)
    memory("requests served", devices[0])
    if "memory" in argv[3:]:
        return 0
    drive = types.SimpleNamespace(
        eng=eng, rid_of=dict(enumerate(rids)),
        output=lambda i: np.asarray(eng.tokens_so_far(rids[i]), np.int32))
    plan = {"requests": requests}
    wrong = []
    for control in check.CONTROLS:
        t0 = time.perf_counter()
        verdict = check.check(ctx, eng.served_weights(), plan, drive,
                              list(range(len(rids))), control=control)
        print(f"[{time.perf_counter() - t0:.0f} s] ok={verdict['ok']} "
              f"{verdict['note']}", flush=True)
        if verdict["ok"] != (control is None):
            wrong.append(control)
    print("wrong verdicts:", wrong or "none", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
