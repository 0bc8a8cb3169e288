"""What lies between an Olmo-Hybrid linear layer's projections and its delta
rule, alone on the chip at the served cell's two shapes (40 decode rows and
one chunk row of 256 tokens, 30 heads of 96 x 192: 11,520 channels, 4 taps,
a history of 48 slot rows a layer, bf16): the Pallas pass
(``paddle_tpu/ops/gdn.py``: ``gdn_prep_step``, ``gdn_prep_chunk``) against
its ``jax.numpy`` spelling (``xla_prep``), each through twelve layers of one
donated history as a tick runs them.

    chiprun --timeout 900 -- python3 benchmarks/gdn_prep_bench.py [cols ...]

With arguments, the pass again at each number of columns a grid step (the
module's ``_PREP_COLS`` is set for the call: this script is where the tiling
is chosen, the program has no option for it). Times are **device** time from
a profiler trace of five calls (the operations' durations summed, read with
``perfbench/tracered.py``: the host takes 2.4 ms to dispatch a call that
returns 37 arrays, twelve times a layer's pass), microseconds a layer, with
the operations that make it up; a microbench, not a benchmark result. Beside
each, the bytes a layer's pass must move (the rows in and out, three history
rows a row in and out) as a share of 819 GB/s, and the pass's distance from
the spelling (q, k, v: the largest difference over the largest value; the
history: equal or not).
"""
import json
import os
import re
import shutil
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from paddle_tpu.ops import gdn                                  # noqa: E402
from perfbench import tracered                                  # noqa: E402

LAYERS, SLOTS, HEADS, DK, DV, TAPS, CHUNK = 12, 40, 30, 96, 192, 4, 256
C = HEADS * (2 * DK + DV)
HBM_GBS = 819.0
BF = jnp.bfloat16


def inputs(seed):
    """Every layer's projection of a tick (the decode rows, then the chunk
    row), the taps and a history whose null slot and rows past the last slot
    are zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (LAYERS, SLOTS + CHUNK, C)).astype(BF)
    taps = jax.random.uniform(ks[1], (TAPS, C), minval=-0.5,
                              maxval=0.5).astype(BF)
    rows = gdn.conv_slot_rows(SLOTS)
    conv = jax.random.normal(ks[2], (LAYERS, TAPS - 1, rows, C)).astype(BF)
    return x, taps, conv.at[:, :, 0].set(0).at[:, :, SLOTS + 1:].set(0)


#: the cell's two row groups, and the chunk row of a tick without a chunk
CASES = {
    "decode 40 rows (37 live)": dict(
        shape=(SLOTS,), slots=[0 if r in (3, 17, 30) else r + 1
                               for r in range(SLOTS)]),
    "chunk row of 256": dict(shape=(1, CHUNK), slots=[7], fresh=[False],
                             row_len=[CHUNK]),
    "chunk row of no token": dict(shape=(1, CHUNK), slots=[0],
                                  fresh=[False], row_len=[0]),
}


def layers_of(prep):
    """Twelve layers' passes over one history, as a tick runs them."""
    def run(x, taps, conv, slots, fresh, row_len):
        outs = []
        for layer in range(LAYERS):
            # the group's rows cut out of the projection, as the tick cuts
            rows = x[layer, :SLOTS] if fresh is None \
                else x[layer, SLOTS:].reshape(1, CHUNK, C)
            *qkv, conv = prep(rows, taps, conv, layer, slots, fresh, row_len,
                              HEADS, DK)
            outs.append(qkv)
        return outs, conv
    return jax.jit(run, donate_argnums=2)


def timed(fn, x, taps, conv, *rest, n=5):
    """``(microseconds of device time a layer, {operation: microseconds})``
    over ``n`` traced calls."""
    outs, conv = fn(x, taps, conv, *rest)
    jax.block_until_ready(conv)
    log = os.path.join("chiprun_out", "gdn_prep_trace")
    shutil.rmtree(log, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log, profiler_options=options)
    for _ in range(n):
        outs, conv = fn(x, taps, conv, *rest)
    jax.block_until_ready((outs, conv))
    jax.profiler.stop_trace()
    doc = tracered.read_xplane(tracered.find_xplane(log))
    shutil.rmtree(log, ignore_errors=True)
    by = defaultdict(float)
    for plane in tracered.device_planes(doc):
        for ev in tracered.op_events(plane):
            by[re.sub(r"\.\d+$", "", tracered.short_name(ev))] += \
                ev["dur_ns"] / 1e3 / n / LAYERS
    top = sorted(by.items(), key=lambda kv: -kv[1])[:6]
    return sum(by.values()), {k: round(v, 2) for k, v in top}


_SPELLING = {}     # case -> the spelling's reading and outputs, taken once


def measure(name, case):
    x, taps, conv = inputs(len(name))
    slots = jnp.asarray(case["slots"], jnp.int32)
    fresh = jnp.asarray(case["fresh"]) if "fresh" in case else None
    row_len = jnp.asarray(case["row_len"], jnp.int32) \
        if "row_len" in case else None
    rest = (slots, fresh, row_len)
    rows = np.prod(case["shape"])
    need = 2 * C * 2 * (rows + (TAPS - 1) * len(case["slots"]))
    out = {}
    ref = None
    for path, prep in (("xla", gdn.xla_prep), ("pallas", gdn.pallas_prep)):
        if path == "xla" and name in _SPELLING:    # no tiling moves it
            out[path], ref = _SPELLING[name]
            continue
        fn = layers_of(prep)
        us, ops = timed(fn, x, taps, jnp.copy(conv), *rest)
        one, left = fn(x, taps, jnp.copy(conv), *rest)
        got = [np.asarray(a, np.float32) for a in one[-1]] + [
            np.asarray(left, np.float32)[:, :, 1:]]
        out[path] = {"us_a_layer": round(us, 2), "of_hbm_peak_pct": round(
            100 * need / HBM_GBS / 1e3 / us, 1), "operations": ops}
        if path == "xla":
            ref = got
            _SPELLING[name] = out[path], ref
            continue
        live = np.asarray(slots) > 0
        if row_len is not None:
            live = live & (np.asarray(row_len) > 0)
        out[path]["qkv_distance"] = [
            float(np.abs(g[live] - r[live]).max(initial=0.0)
                  / max(np.abs(r[live]).max(initial=0.0), 1e-9))
            for g, r in zip(got[:3], ref[:3])]
        out[path]["history_equal"] = bool(np.array_equal(got[3], ref[3]))
        out[path]["finite"] = bool(all(np.isfinite(g).all() for g in got))
    return out


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("gdn_prep_bench measures the chip; this is "
                         + dev.platform)
    out = {"device": dev.device_kind}
    for cols in [gdn._PREP_COLS] + [int(a) for a in sys.argv[1:]]:
        gdn._PREP_COLS = cols
        tile = gdn._prep_cols(HEADS, DK, DV)[1]
        for name, case in CASES.items():
            key = f"{name}, {tile} columns a step"
            try:
                out[key] = measure(name, case)
            except Exception as e:       # a tiling Mosaic refuses: say so
                out[key] = {"refused": str(e).splitlines()[0][:200]}
            print(json.dumps({key: out[key]}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gdn_prep_bench.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
