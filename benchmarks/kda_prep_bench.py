"""What lies between Solar-Open2's three KDA projections and the scan, alone
on the chip at the training cell's shapes (``[1, 8192, 8192]`` bf16, heads
of 128, 4 taps): the Pallas pair ``kda_prep``/``kda_prep_bwd``
(``paddle_tpu/ops/kda_prep.py``) against its ``jax.numpy`` spelling, forward
and forward + ``jax.vjp``, and the pair again at other tilings.

    chiprun --timeout 900 -- python3 benchmarks/kda_prep_bench.py \
        [rows:cols ...]

With arguments, the pair at each tiling named after the module's own (the
module's constants are set for the call: this script is where a tiling is
chosen, the program has no option for it). Times are host-clock means of
calls that end in ``block_until_ready``; a microbench, not a benchmark
result. The bytes a pass must move (the projections in, the operands out;
both and the cotangents in, the gradients out) are printed beside each time
as a share of 819 GB/s.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402

from paddle_tpu.ops import kda_prep as kp                       # noqa: E402

B, S, HEADS, D, TAPS, EPS = 1, 8192, 64, 128, 4, 1e-6
NORMS = (True, True, False)
HBM_GBS = 819.0


def inputs(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    bf = lambda a: a.astype(jnp.bfloat16)
    ps = tuple(bf(jax.random.normal(k, (B, S, HEADS * D))) for k in ks[:3])
    ws = tuple(bf(jax.random.uniform(k, (TAPS, HEADS * D), minval=-0.5,
                                     maxval=0.5)) for k in ks[3:6])
    cs = tuple(bf(jax.random.normal(k, (B, S, HEADS * D))) for k in ks[6:])
    return ps, ws, cs


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def measure(chain, ps, ws, cs):
    fwd = jax.jit(lambda ps, ws: chain(ps, ws, NORMS, D, EPS))

    def both(ps, ws, cs):
        out, vjp = jax.vjp(lambda p, w: chain(p, w, NORMS, D, EPS), ps, ws)
        return out, vjp(cs)

    one = 2 * B * S * HEADS * D          # one bf16 array's bytes
    arrays = {"fwd_ms": 6, "fwd_bwd_ms": 15, "bwd_ms": 9}
    got = {"fwd_ms": timed(fwd, ps, ws),
           "fwd_bwd_ms": timed(jax.jit(both), ps, ws, cs)}
    got["bwd_ms"] = got["fwd_bwd_ms"] - got["fwd_ms"]
    return {k: {"ms": round(v, 3), "of_hbm_peak_pct": round(
        100 * arrays[k] * one / HBM_GBS / 1e6 / v, 1)}
        for k, v in got.items()}


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("kda_prep_bench measures the chip; this is "
                         + dev.platform)
    ps, ws, cs = inputs(0)
    out = {"device": dev.device_kind,
           "xla": measure(kp.xla_kda_prep, ps, ws, cs)}
    print(json.dumps({"xla": out["xla"]}), flush=True)
    tilings = [(kp._ROWS, kp._COLS)] + [
        tuple(int(n) for n in a.split(":")) for a in sys.argv[1:]]
    for rows, cols in tilings:
        kp._ROWS, kp._COLS = rows, cols
        name = f"pallas {rows}:{cols}"
        try:
            out[name] = measure(kp.pallas_kda_prep, ps, ws, cs)
        except Exception as e:           # a tiling Mosaic refuses: say so
            out[name] = {"refused": str(e).splitlines()[0][:200]}
        print(json.dumps({name: out[name]}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_prep_bench.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
