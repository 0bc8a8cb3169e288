"""The grouped matmul alone, on the chip, at the shapes the benchmark's cells
give it and with each cell's rows a group: one ``moe_gmm`` call by the tile
the powers of two gave (the rule before PR 50), by the rule's tile
(``ops/grouped_matmul.tile_for``) and by the rule under other budgets of
weights a grid step. **This script is where the budget was chosen; the
program has no option for it.**

    chiprun --timeout 1500 -- python3 benchmarks/grouped_matmul_bench.py \
        [weights a step ...]

Six shapes: the serving ticks of Ling-3.0-flash (``[2560, 768]`` experts, 128
held, a window of 1,024 rows: ~70 experts touched by one or two rows; and the
same with a tick's 256 pad tokens piled on two held experts, which is what a
tick without a chunk routes), dots3 (``[5120, 1536]``, 32 held, ~8 rows each)
and DeepSeek-V2 (the same experts, 20 held, ~20 rows each), and the training
steps of OLMoE (``[2048, 1024]``, 64 experts of ~512 rows) and Solar-Open2
(``[4096, 1280]``, 8 held of ~205 rows; for the two trainings ``moe_tgmm``,
the gradient towards the weights, too). Without arguments the budgets are
2 M (the module's), 4 M and 8 M weights.

Times are **device** time from a profiler trace of ten calls (the kernel's
events alone, read with ``perfbench/tracered.py``), microseconds a call, and
beside them the touched groups' weights over that time in GB/s (819 is the
v5e's HBM peak; a training shape is bound by the MXU, not by this) and the
output's distance from the first tile's. A microbench, not a benchmark
result.
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

from paddle_tpu.ops import grouped_matmul as gm                 # noqa: E402
from perfbench import tracered                                  # noqa: E402

BF = jnp.bfloat16
CALLS = 10


def tile_before(m, k, n):
    """``tile_for`` as it stood until PR 50: columns, then contraction, from
    five powers of two under 2 M weights."""
    sizes = (2048, 1024, 512, 256, 128)
    tn = next(s for s in sizes if n % s == 0)
    tk = next(s for s in sizes if k % s == 0 and s * tn <= 2 ** 21)
    return 128, tk, tn


def _ling_rows(rng, pad):
    sizes = np.zeros(128, np.int64)
    touched = rng.choice(128, 70, replace=False)
    sizes[touched] = rng.integers(1, 3, 70)
    if pad:                   # 256 identical tokens, two of their 8 held here
        sizes[touched[:2]] += 256
    return sizes


def _about(rng, groups, rows):
    return np.maximum(rng.poisson(rows, groups), 0)


#: name -> (window rows, H, F, rows of each group, training cell or not)
SHAPES = {
    "ling tick (70 of 128 touched, 1-2 rows)":
        (1024, 2560, 768, lambda r: _ling_rows(r, False), False),
    "ling tick + 256 pad tokens on two experts":
        (1024, 2560, 768, lambda r: _ling_rows(r, True), False),
    "dots3 tick (32 held, ~8 rows)":
        (512, 5120, 1536, lambda r: _about(r, 32, 8.4), False),
    "dsv2 tick (20 held, ~20 rows)":
        (640, 5120, 1536, lambda r: _about(r, 20, 20), False),
    "olmoe step (64 of ~512 rows)":
        (32768, 2048, 1024, lambda r: r.multinomial(32768, [1 / 64] * 64),
         True),
    "solar step (8 held, ~205 rows)":
        (2560, 4096, 1280, lambda r: _about(r, 8, 205), True),
}


def device_ops_us(fn, *args):
    """{operation: microseconds of device time a call} over ``CALLS`` traced
    calls, an operation named without its number (``moe_gmm`` of
    ``moe_gmm.12``); ``benchmarks/combine_bench.py`` reads it too."""
    jax.block_until_ready(fn(*args))
    log = os.path.join("chiprun_out", "gmm_bench_trace")
    shutil.rmtree(log, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log, profiler_options=options)
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    doc = tracered.read_xplane(tracered.find_xplane(log))
    shutil.rmtree(log, ignore_errors=True)
    by_name = defaultdict(float)
    for plane in tracered.device_planes(doc):
        for ev in tracered.op_events(plane):
            by_name[re.sub(r"\.\d+$", "", tracered.short_name(ev))] += \
                ev["dur_ns"] / 1e3 / CALLS
    return dict(by_name)


def device_us(fn, *args):
    """Microseconds of device time a call: the ``moe_`` kernel's events
    (the three small gathers of ``_visits`` beside them are not the
    kernel's)."""
    return sum(us for name, us in device_ops_us(fn, *args).items()
               if name.startswith("moe_"))


def tiles_of(m, k, n, budgets):
    """{label: tile}: the old rule's, then the rule's under each budget."""
    out = {"before PR 50": tile_before(m, k, n)}
    kept = gm._TILE_WEIGHTS
    for budget in budgets:
        gm._TILE_WEIGHTS = budget
        out[f"rule at {budget // 2 ** 20} M" + (
            " (the module's)" if budget == kept else "")] = gm.tile_for(m, k,
                                                                        n)
    gm._TILE_WEIGHTS = kept
    return out


def measure(name, budgets):
    m, h, f, rows_of, training = SHAPES[name]
    rng = np.random.default_rng(len(name))
    sizes = np.asarray(rows_of(rng), np.int64)
    while sizes.sum() > m:                  # a draw over the window: shave
        sizes[np.argmax(sizes)] -= sizes.sum() - m
    e, live = len(sizes), int(sizes.sum())
    touched = int((sizes > 0).sum())
    group_sizes = jnp.asarray(sizes, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(live), 3)
    out = {"window_rows": m, "expert": [h, f], "groups": e,
           "touched": touched, "live_rows": live}
    for what, k, n in (("gate/up", h, f), ("down", f, h)):
        lhs = jax.random.normal(ks[0], (m, k), jnp.float32).astype(BF)
        rhs = (jax.random.normal(ks[1], (e, k, n), jnp.float32)
               * 0.02).astype(BF)
        other = jax.random.normal(ks[2], (m, n), jnp.float32).astype(BF)
        read = 2 * k * n * touched
        first, seen = None, {}
        for label, tile in tiles_of(m, k, n, budgets).items():
            if tile in seen:                # measured under another label
                line = seen[tile]
            else:
                line = seen[tile] = {
                    "tile": list(tile),
                    "steps_a_visit": (k // tile[1]) * (n // tile[2])}
                try:
                    fn = jax.jit(lambda a, b, s, t=tile: gm._gmm(a, b, s, t,
                                                                 False))
                    us = device_us(fn, lhs, rhs, group_sizes)
                    got = np.asarray(fn(lhs, rhs, group_sizes)[:live],
                                     np.float32)
                    first = got if first is None else first
                    line.update(
                        us=round(us, 1),
                        touched_weights_gb_s=round(read / us / 1e3, 1),
                        tflops=round(2 * live * k * n / us / 1e6, 2),
                        from_first=float(np.abs(got - first).max()
                                         / np.abs(first).max()))
                    if training:            # the weights' gradient as well
                        tg = jax.jit(lambda a, b, s, t=tile: gm._tgmm(
                            a, b, s, t))
                        line["tgmm_us"] = round(device_us(
                            tg, lhs, other, group_sizes), 1)
                except Exception as ex:     # a tile Mosaic refuses: say so
                    line["refused"] = str(ex).splitlines()[0][:200]
            out[f"{what} [{k}, {n}] {label}"] = line
            print(json.dumps({name: {f"{what} {label}": line}}), flush=True)
    return out


def main(argv):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("grouped_matmul_bench measures the chip; this is "
                         + dev.platform)
    budgets = [int(a) for a in argv] or [2 ** 21, 2 ** 22, 2 ** 23]
    out = {"device": dev.device_kind, "budgets": budgets}
    for name in SHAPES:
        out[name] = measure(name, budgets)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/grouped_matmul_bench.json", "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
