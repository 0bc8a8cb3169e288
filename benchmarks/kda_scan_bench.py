"""Solar-Open2's gated delta-rule scan alone on the chip at the training
cell's shapes (one layer of one sequence: ``[1, 8192, 64 x 128]`` bf16, ``g``
float32), a sweep at a time: what ranks what the forward rule should leave
the backward sweep (``paddle_tpu/ops/kda.py``, "The backward pass").

    chiprun --timeout 900 -- python3 benchmarks/kda_scan_bench.py \
        [path/to/another/checkout/paddle_tpu/ops/kda.py]

Sweeps timed, each a Pallas call over 128 chunks x 64 heads:

* ``fwd``: ``kda_fwd``, the forward sweep that writes ``o`` alone;
* ``fwd_states``: the same body leaving the state before every chunk too;
* ``fwd_states_inv``: the forward rule as the program has it
  (``kda_fwd_states``: the states and each chunk's triangular inverse);
* ``bwd_grads``: the backward sweep making the inverse again (the parent's
  ``kda_bwd_grads``: the program's body told the inverse is absent), and
  ``bwd_states``: the sweep PR 42 deleted, the forward recurrence run once
  more to write the states (the parent's ``kda_bwd_states``; its kernel
  lives on here, for this comparison);
* ``bwd_grads_inv``: ``kda_bwd_grads`` as the program has it, the inverse an
  operand. The inverses never cross a ``jit``'s edge here (XLA would lay a
  ``[..., 64, 64]`` array out otherwise there and copy it, which no step
  does: forward rule and backward sweep sit in one program), so this one is
  ``chain_inv`` (forward rule, then backward sweep, one ``jit``) less
  ``fwd_states_inv``; ``chain`` is the same pair with no inverse left.

Then ``pallas_kda`` whole, forward and forward + ``jax.vjp``, of this
checkout and, with an argument, of the ``kda.py`` named (the parent's, from
``git archive``). The two spellings of the backward sweep must give the same
bits; the script says so or fails. Times are host-clock means of calls that
end in ``block_until_ready``; a microbench, not a benchmark result.
"""
import functools
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
from jax.experimental import pallas as pl                       # noqa: E402
from jax.experimental.pallas import tpu as pltpu                # noqa: E402

from paddle_tpu.ops import kda                                  # noqa: E402

B, S, HEADS, D = 1, 8192, 64, 128
NC = S // kda.CHUNK
F32 = jnp.float32


def inputs(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (B, S, HEADS, D)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    flat = lambda a: a.reshape(B, S, HEADS * D).astype(jnp.bfloat16)
    q, k = (flat(unit(jax.random.normal(ks[i], shape))) for i in (0, 1))
    v, do = (flat(jax.random.normal(ks[i], shape)) for i in (2, 3))
    g = jax.random.uniform(ks[4], (B, S, HEADS * D), minval=-1.0,
                           maxval=-1e-3)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (B, S, HEADS)))
    return (q, k, v, g, beta), do


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / n * 1e3, 3)


class _Absent:
    """A residual the forward rule did not leave: ``ref[...]`` is None, and
    ``kda._chunk_parts`` then makes the inverse itself."""
    def __getitem__(self, at):
        return None


def _states_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, s_ref, *, hb):
    """The parent's ``kda_bwd_states``: the forward recurrence, writing the
    state before every chunk and nothing else."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    for h in range(hb):
        s0 = s_ref[h]
        st_ref[0, h, 0] = s0
        s_ref[h] = kda._chunk_fwd(
            *(kda._head_cols(r, h, D) for r in (q_ref, k_ref, v_ref, g_ref)),
            b_ref[0, h, pl.ds(c, 1), :], s0, 1.0)[1]


def _grads_kernel(*refs, **kw):
    """The parent's ``kda_bwd_grads``: no inverse among the operands."""
    kda._grads_kernel(*refs[:6], _Absent(), *refs[6:], **kw)


def sweeps(scale):
    """name -> jitted function of (q, k, v, g, beta[, states][, do])."""
    hb = kda._heads_a_step(HEADS)
    arr = lambda d, dt: jax.ShapeDtypeStruct((B, S, HEADS * d), dt)
    state = jax.ShapeDtypeStruct((B, HEADS, NC, D, D), F32)
    bf = jnp.bfloat16

    def call(kernel, name, order, more_in, out_specs, out_shape):
        cols, beta, left = kda._specs(S, D, D, hb, order)
        ins = [cols(D)] * 4 + [beta] + [left[0], cols(D)][:more_in]
        outs = [{"cols": cols(D), "beta": beta, "state": left[0]}[o]
                for o in out_specs]
        fn = pl.pallas_call(
            kernel, name=name, grid=(B, HEADS // hb, NC), in_specs=ins,
            out_specs=outs, out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((hb, D, D), F32)],
            compiler_params=kda._params(), interpret=kda._interpret())
        return jax.jit(lambda q, k, v, g, beta, *more: fn(
            q, k, v, g, kda._beta_blocks(beta), *more))

    fwd_kernel = functools.partial(kda._fwd_kernel, hb=hb, dk=D, dv=D,
                                   scale=scale)
    fwd_states = call(fwd_kernel, "kda_fwd_states", lambda c: c, 0,
                      ["cols", "state"], [arr(D, bf), state])
    bwd_grads = call(
        functools.partial(_grads_kernel, hb=hb, dk=D, dv=D, scale=scale,
                          n_chunks=NC),
        "kda_bwd_grads", lambda c: NC - 1 - c, 2, ["cols"] * 4 + ["beta"],
        [arr(D, bf)] * 3 + [arr(D, F32), jax.ShapeDtypeStruct(
            (B, HEADS, NC, kda.CHUNK), F32)])
    rule = functools.partial(kda._pallas_fwd, scale=scale, leave=True)

    def chain(*a):
        o, states = fwd_states(*a[:5])
        *grads, db = bwd_grads(*a[:5], states, a[5])
        return (o, states, *grads,
                jnp.transpose(db, (0, 2, 3, 1)).reshape(B, S, HEADS))

    def chain_inv(*a):
        o, states, invs = rule(*a[:5])
        return (o, states) + tuple(kda._pallas_bwd(*a[:5], states, invs,
                                                   a[5], scale))

    return {
        "fwd": jax.jit(functools.partial(kda._pallas_fwd, scale=scale,
                                         leave=False)),
        "fwd_states": fwd_states,
        "fwd_states_inv": jax.jit(lambda *a: rule(*a)[:2]),
        "bwd_states": call(functools.partial(_states_kernel, hb=hb),
                           "kda_bwd_states", lambda c: c, 0, ["state"],
                           [state]),
        "bwd_grads": bwd_grads,
        "chain": jax.jit(chain),
        "chain_inv": jax.jit(chain_inv),
    }


def whole(module, args, do, scale):
    """``pallas_kda`` of ``module``: forward, and forward + ``jax.vjp``."""
    fwd = jax.jit(lambda *a: module.pallas_kda(*a, scale))

    def both(*a):
        out, vjp = jax.vjp(lambda *x: module.pallas_kda(*x, scale), *a[:-1])
        return out, vjp(a[-1])

    got = {"fwd_ms": timed(fwd, *args),
           "fwd_bwd_ms": timed(jax.jit(both), *args, do)}
    got["bwd_ms"] = round(got["fwd_bwd_ms"] - got["fwd_ms"], 3)
    return got


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("kda_scan_bench measures the chip; this is "
                         + dev.platform)
    run(dev, sys.argv[1:])


def run(dev, others):
    args, do = inputs(0)
    scale = D ** -0.5
    fns = sweeps(scale)
    o, states = fns["fwd_states_inv"](*args)
    out = {"device": dev.device_kind, "states_bytes": states.nbytes}
    operands = {"bwd_grads": args + (states, do), "chain": args + (do,),
                "chain_inv": args + (do,)}
    for name, fn in fns.items():
        out[name + "_ms"] = timed(fn, *operands.get(name, args))
        print(json.dumps({name + "_ms": out[name + "_ms"]}), flush=True)
    out["bwd_grads_inv_ms"] = round(
        out["chain_inv_ms"] - out["fwd_states_inv_ms"], 3)
    print(json.dumps({"bwd_grads_inv_ms": out["bwd_grads_inv_ms"]}),
          flush=True)
    # the three sweeps that write the states, the two that write o alone or
    # not, and one spelling of the backward sweep against the other
    with_inv = fns["chain_inv"](*args, do)
    without = fns["chain"](*args, do)
    same = {"states": bool(
                jnp.array_equal(states, fns["bwd_states"](*args)[0])
                and jnp.array_equal(states, without[1])),
            "o": bool(jnp.array_equal(o, fns["fwd"](*args))),
            "grads": all(bool(jnp.array_equal(a, b))
                         for a, b in zip(with_inv, without))}
    out["same_bits"] = same
    print(json.dumps({"same_bits": same}), flush=True)
    out["this_checkout"] = whole(kda, args, do, scale)
    print(json.dumps({"this_checkout": out["this_checkout"]}), flush=True)
    for path in others:
        spec = importlib.util.spec_from_file_location(
            "paddle_tpu.ops._other_kda", path)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        out[path] = whole(other, args, do, scale)
        print(json.dumps({path: out[path]}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_scan_bench.json", "w") as f:
        json.dump(out, f, indent=1)
    if not all(same.values()):
        raise SystemExit("the sweeps disagree: " + json.dumps(same))


if __name__ == "__main__":
    main()
