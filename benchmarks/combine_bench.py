"""The held experts' combine alone, on the chip, at the four windows
``distributed/moe._held_experts`` meets in the benchmark's cells: a window's
``rows`` ``[width, H]`` added to their tokens' ``y`` ``[T, H]`` by
``y.at[tok].add(rows)`` (``scatter``) and by ``moe._combine_onehot``
(``onehot``: a 0/1 ``[T, width]`` operand times the rows on the MXU, summed in
float32): the two forms ``moe._combine`` picks between by ``t * h``. Alone, a
scatter-add pays a copy of ``y`` that the in-place one inside a step does not
(Solar's: 0.97 ms here, 0.66 in the step; PERF.md section 6, PR 52).

    chiprun --timeout 900 -- python3 benchmarks/combine_bench.py [out.json]

The routing is drawn: each token's ``top_k`` experts without replacement, the
first ``held`` of ``e`` kept, rows sorted by expert as ``held_moe`` sorts them,
cut to the window. Times are **device** time from a profiler trace of ten
calls (every operation of the jitted form,
``grouped_matmul_bench.device_ops_us``), microseconds a call, the three
largest operations named. A microbench, not a benchmark result.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from paddle_tpu.distributed import moe                          # noqa: E402
from grouped_matmul_bench import device_ops_us                  # noqa: E402

BF = jnp.bfloat16

#: name -> (T, width, H, top_k, held, e): what ``held_moe`` hands
#: ``_held_experts`` in each cell (``held_window_rows`` gives the widths)
SHAPES = {
    "dsv2 tick": (532, 640, 5120, 6, 20, 160),
    "dots3 tick": (268, 512, 5120, 8, 32, 256),
    "ling tick": (320, 1024, 2560, 8, 128, 512),
    "solar step": (8192, 2560, 4096, 8, 8, 320),
}


def routing(rng, t, width, top_k, held, e):
    """``(tok [width], live rows)``: the first window of a drawn routing's
    held rows, sorted by expert; a row past the end has token 0."""
    experts = np.argsort(rng.random((t, e)), axis=1)[:, :top_k].T   # [K, T]
    here = experts < held
    order = np.argsort(np.where(here, experts, e).reshape(-1), kind="stable")
    live = min(int(here.sum()), width)
    tok = np.zeros(width, np.int64)
    tok[:live] = order[:live] % t
    return jnp.asarray(tok, jnp.int32), live


def scatter(y, tok, rows):
    return y.at[tok].add(rows)


def onehot(y, tok, rows):
    return moe._combine_onehot(y, tok, rows)


def device_us(fn, *args):
    """(microseconds of device time a call, its three largest operations)."""
    by_name = device_ops_us(fn, *args)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:3]
    return sum(by_name.values()), {k: round(v, 1) for k, v in top}


def measure(name):
    t, width, h, top_k, held, e = SHAPES[name]
    rng = np.random.default_rng(len(name))
    tok, live = routing(rng, t, width, top_k, held, e)
    ks = jax.random.split(jax.random.PRNGKey(live), 2)
    y = jax.random.normal(ks[0], (t, h), jnp.float32).astype(BF)
    rows = jax.random.normal(ks[1], (width, h), jnp.float32).astype(BF)
    rows = jnp.where((jnp.arange(width) < live)[:, None], rows, 0)
    want = np.asarray(scatter(y.astype(jnp.float32), tok,
                              rows.astype(jnp.float32)))
    out = {"T": t, "width": width, "H": h, "live_rows": live,
           "onehot_gflop": round(2 * t * width * h / 1e9, 2)}
    for form in (scatter, onehot):
        fn = jax.jit(form)
        us, top = device_us(fn, y, tok, rows)
        got = np.asarray(fn(y, tok, rows), np.float32)
        out[form.__name__] = {"us": round(us, 1), "largest": top,
                              "from_float32": float(np.abs(got - want).max())}
        print(json.dumps({name: {form.__name__: out[form.__name__]}}),
              flush=True)
    return out


def main(argv):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("combine_bench measures the chip; this is "
                         f"{dev.platform}")
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "shapes": {name: measure(name) for name in SHAPES}}
    path = argv[1] if len(argv) > 1 else os.path.join(
        "chiprun_out", "combine_bench.json")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv)
