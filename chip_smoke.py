"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process, no arguments. Drives the two normal entry points once at the
full width of GPT-3 1.3B (h2048 x L24 x 16 heads, head_dim 128, seq 2048,
vocab 50304; weights random from a seed) on whatever accelerator jax
finds, and checks what comes out by the repo's own means:

  0 device    a TPU that the one peak table knows; memory_stats reports;
              every Pallas gate reads "compile with Mosaic"
  1 kernels   flash fwd+bwd and the ragged paged kernel (bf16 and int8
              pools, at the serving cells' row shapes: 12 rows over 128
              pages a slot, 10 over 32), compiled by Mosaic, against their
              references
  1b experts  the drop-less expert layer at OLMoE's widths (64 experts of
              1024, 8 a token, 4096 tokens), forward and gradients against
              the float32 reference, balanced and with one expert forced
              to over four times its share: nothing is dropped, and the
              grouped products took the path production takes here, the
              Pallas kernels moe_gmm/moe_tgmm
  1c solar    one step of the small Solar-Open2 preset (a period of one
              gated grouped-query softmax layer and three gated delta-rule
              layers, 16 experts of which 8 held) through the trainer in
              bf16: the loss against the float32 reference, and the scan
              took the path production takes here, the Pallas kernels
              kda_fwd/kda_bwd_*; the chain between the projections and
              the scan (kda_prep/kda_prep_bwd) at the training cell's
              [1, 8192, 8192] bf16 against its jax.numpy spelling
  1d head     the fused lm-head + cross-entropy at the four training
              cells' shapes (tied [V, H] and Linear [H, V], a vocabulary
              shard of 25152 as 6.7B's): loss and both gradients against
              autodifferentiation of the float32 reference, and the
              program traced the rule that makes the gradients in the
              forward pass
  2 serve     ServingEngine.submit/step/run, ten requests over ~150 ticks;
              the tick took its attention through the ragged kernel
  2d dsv2     DeepSeek-V2's dense latent attention (ops/latent_attention.
              latent_attention) at its cell's row shapes (two chunk rows of
              256, twenty decode rows; 128 heads over latents of 576) through
              both of its spellings against a float32 softmax, and a small
              model (YaRN, a router limited to 2 of 4 groups, held experts)
              through the engine, two chunks a tick, against the float32
              reference models/deepseek_v2_reference.py; its cell is
              serve-dsv2-docqa-backlog (5 of 60 layers, 20 of 160 experts,
              1/8 of the vocabulary)
  3 train     HybridPipelineTrainer.step, bench.py's headline knobs
  4 multichip the same trainer on dp2 x tp2 and pp2 x tp2 (and the head
              alone as that arm runs it: inside a region manual over pp,
              its vocabulary over tp, gradients against the reference),
              and the ZeRO / int8-ring arms of compile_train_step on dp=4
              (only with >= 4 devices)

There is no CPU mode: with no accelerator it exits non-zero and prints no
result. A failed phase does not stop the later ones, but the exit code is
non-zero and the last line says which failed. The last line of stdout on
success is ``{"ok": true, "device": {...}}``.

What it prints are smoke observations, not benchmark results. The phase
bodies take a model config and sizes so that tests/test_chip_smoke.py can
rehearse them at toy sizes on the CPU mesh.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time
import traceback
import warnings

import numpy as np

# normalised max error |a - ref|_inf / |ref|_inf allowed between a Pallas
# kernel and its reference evaluated in f32 at "highest" matmul precision
# on the same inputs. The kernels round probabilities and outputs to bf16
# (2^-8 relative) and accumulate in f32; backward rounds ds to bf16 too.
TOL_FLASH_FWD = 2e-2
TOL_FLASH_BWD = 3e-2
TOL_RAGGED = 2e-2
# the drop-less expert layer against models/olmoe_reference.moe in f32 at
# "highest" on the same bf16-rounded inputs: the program rounds the gated
# product, each expert's output and the routing weight to bf16 (2^-8 each,
# up to 8 experts summed a token); the gradients pass through those
# roundings once more. Both route on the same bf16 inputs with f32
# accumulation, so a token whose 8th choice differs (it would err by about
# an eighth of its output) is not expected and not allowed for.
TOL_EXPERTS_FWD = 2e-2
TOL_EXPERTS_BWD = 3e-2

# the fused head against autodifferentiation of the float32 reference at
# "highest" on the same bf16-rounded inputs. The loss is float32 sums of
# exact products either way. ``dx`` is rounded to bf16 once; ``dW`` is a
# bf16 running sum over the sequence's chunks of 256 (8 to 32 of them), one
# rounding an add, as the transposed scan's was before the gradients moved
# into the forward pass. Seen on the v5e, PR 36, at the four cells' shapes:
# loss 3e-8 to 1e-7, dx 2.4e-3 to 5.0e-3, dW 6.8e-3 to 9.2e-3.
TOL_HEAD_LOSS = 1e-5
TOL_HEAD_GRAD = 3e-2
#: (rows, sequence, hidden, vocabulary, weight is [V, H]) of the head in
#: the four training cells: 1.3B, OLMoE, Solar-Open2, and one chip's
#: vocabulary shard of 6.7B on pp2 x tp2
HEAD_SHAPES = ((12, 2048, 2048, 50304, True), (8, 4096, 2048, 50304, False),
               (2, 8192, 4096, 24576, False), (48, 2048, 4096, 25152, True))

#: the most a ``submit()`` may take while ticks are in flight, ms: it is
#: tens of microseconds, and was two to three ticks (137 ms at 1.3B) while
#: the request's sampling key was folded on the serving device
SUBMIT_LIMIT_MS = 5.0

#: (arrival tick, prompt length, new tokens, length of the prefix shared
#: with the other requests that name one). Ten requests on eight slots:
#: admission, chunked prefill (32 tokens a tick, so 1500 is 47 chunks),
#: mixed and decode-only ticks, prefix aliasing (request 7 arrives after
#: request 1 published its 256-token prefix) and slot reuse all happen.
SERVE_REQUESTS = (
    (0, 16, 32, 0), (0, 300, 48, 256), (0, 1500, 32, 0), (3, 64, 64, 0),
    (5, 130, 40, 0), (8, 700, 32, 0), (12, 37, 33, 0), (90, 356, 48, 256),
    (100, 20, 32, 0), (110, 512, 32, 0))


#: the looped pass of phase ``serve`` against models/ouro_reference.py (one
#: full float32 forward over prompt and output): how far below its
#: position's largest logit an emitted token's may lie, and how far a
#: request's mean expected exit step from the reference's. For the 12
#: cache layers of this pass (3 layers x 4 steps): bf16 moves a logit by
#: about 0.015 rms there and doubles with the depth (PERF.md section 6), so
#: these are not the limits of perfbench/checks/ouro_serve.py, which holds
#: the whole model's 192 to 2.0 and 0.04.
TOL_LOOP_SHORTFALL = 0.15
TOL_LOOP_EXIT = 0.02
#: (prompt length, new tokens) of the looped pass: chunked prefill (32 a
#: tick), mixed and decode-only ticks, three requests on two slots
LOOP_REQUESTS = ((40, 24), (100, 16), (17, 32))
# the latent-attention pass (ISSUE 37; models/dots3.py): the read sides of
# the latent pools against plain float32 spellings (bf16 products, float32
# accumulated), and a small model's emitted tokens against the float32
# reference: the shortfall of an emitted token's logit below the reference's
# largest, and the share of a selected set that differs from the reference's
TOL_LATENT_OPS = 2e-2
TOL_LATENT_SHORTFALL = 0.25
#: ... of the median emitted token; the worst may lie this far below: the
#: small model selects 8 keys a query, so one near-tie that bf16 indexer keys
#: order otherwise moves an eighth of a query's attention (2.13 read on the
#: chip, PR 37; perfbench/checks/dots3_serve.py has the published widths')
TOL_LATENT_WORST = 4.0
TOL_LATENT_SELECTED = 0.3
LATENT_REQUESTS = ((23, 12), (41, 10), (7, 16))


class SmokeFailure(AssertionError):
    """A check of this script that did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def memory_stat(key: str):
    import jax

    stats = jax.local_devices()[0].memory_stats()
    return None if not stats else stats.get(key)


def peak_bytes():
    return memory_stat("peak_bytes_in_use")


def _gb(n) -> str:
    return "n/a" if n is None else f"{n / 1e9:.2f} GB"


def on_default_platform(tree, what: str) -> None:
    """Every array of ``tree`` lives on devices of the platform jax chose
    (phase 0 has established that this is the TPU): core/place.py falls
    back to "any device" quietly, and this is where that would show."""
    import jax

    want = jax.devices()[0].platform
    for leaf in jax.tree_util.tree_leaves(tree):
        got = {d.platform for d in leaf.devices()}
        check(got == {want}, f"{what}: array on {got}, expected {want}")


def release_device_memory() -> None:
    """bench.py's release_hbm: reference cycles and the jit/executable
    caches both pin device buffers."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


# ---------------------------------------------------------------------------
# phase 0: device
# ---------------------------------------------------------------------------
def phase_device(cache_dir: str) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.profiler.instrument import device_stamp
    from paddle_tpu.profiler.peaks import device_peak
    from paddle_tpu.utils.compile_cache import cache_entries

    devs = jax.devices()
    dev = devs[0]
    say("device", f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {md.version('libtpu')} python "
        f"{sys.version.split()[0]}")
    say("device", f"platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: jax found no TPU (devices: {devs}); "
                 "this script has no CPU mode")
    peak = device_peak(dev)       # unknown device_kind raises
    say("device", f"peak {peak.bf16_flops / 1e12:.0f} TFLOP/s bf16 "
        f"({peak.source})")
    stats = dev.memory_stats()
    check(stats and "peak_bytes_in_use" in stats,
          f"memory_stats() has no peak_bytes_in_use: {stats}")
    say("device", f"memory_stats: bytes_limit={_gb(stats.get('bytes_limit'))}"
        f" peak_bytes_in_use={_gb(stats['peak_bytes_in_use'])}")
    check(not fa._interpret() and not pa._interpret(),
          "a Pallas gate reads interpret mode on a TPU backend")
    say("device", f"compile cache {cache_dir}: "
        f"{cache_entries(cache_dir)} entries at start")
    from paddle_tpu.core import native

    came_with_tree = os.path.exists(native._SO)
    say("device", "native runtime library: " + (
        "absent (python fallbacks)" if not native.available()
        else "came with the tree" if came_with_tree
        else "built here from native/ by its Makefile")
        + "; nothing below depends on it")
    return device_stamp()


# ---------------------------------------------------------------------------
# phase 1: kernels against their references
# ---------------------------------------------------------------------------
def _nerr(a, ref) -> float:
    a = np.asarray(a, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(a - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _mosaic_check(fn, args, interpret: bool, what: str) -> None:
    """The lowered program holds a Mosaic call wherever the kernel's own
    gate says it is not interpreted."""
    import jax

    if not interpret:
        check("tpu_custom_call" in jax.jit(fn).lower(*args).as_text(),
              f"{what}: no tpu_custom_call in the lowered program")


def check_flash(b: int, s: int, h: int, d: int, dtype) -> dict:
    """Flash fwd+bwd at [b, s, h, d] against ``mha_reference`` evaluated
    in f32 at highest precision on the same (dtype-rounded) inputs."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import flash_attention as fa

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(kk, (b, s, h, d), jnp.float32)
                  .astype(dtype) for kk in ks)

    def kernel(q_, k_, v_):
        return fa._flash_mha(q_, k_, v_, True, None)

    def loss_of(f):
        return lambda q_, k_, v_: jnp.sum(
            f(q_, k_, v_).astype(jnp.float32) * w.astype(jnp.float32))

    def reference(q_, k_, v_):
        return fa.mha_reference(q_, k_, v_, causal=True)

    _mosaic_check(jax.grad(loss_of(kernel), (0, 1, 2)), (q, k, v),
                  fa._interpret(), "flash fwd+bwd")
    out = jax.jit(kernel)(q, k, v)
    grads = jax.jit(jax.grad(loss_of(kernel), (0, 1, 2)))(q, k, v)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(reference)(*f32)
        ref_g = jax.jit(jax.grad(loss_of(reference), (0, 1, 2)))(*f32)
    jax.block_until_ready((out, grads, ref, ref_g))
    errs = {"fwd": _nerr(out, ref)}
    errs.update({n: _nerr(g, rg)
                 for n, g, rg in zip(("dq", "dk", "dv"), grads, ref_g)})
    check(np.isfinite(list(errs.values())).all(), f"flash non-finite {errs}")
    check(errs["fwd"] <= TOL_FLASH_FWD, f"flash fwd error {errs}")
    check(max(errs["dq"], errs["dk"], errs["dv"]) <= TOL_FLASH_BWD,
          f"flash bwd error {errs}")
    return errs


def check_ragged(num_pages: int, ps: int, nh: int, hd: int, nps: int,
                 t: int, int8: bool, rows: int = 4) -> float:
    """``ragged_paged_attention(impl="pallas")`` against ``impl="xla"``
    in f32 on the same pools: ``rows`` rows of ``t`` queries with ragged
    pos0/true_len (four patterns, each later round of them a third of the
    capacity further on), null-page table entries behind each row's
    frontier, and rows that end exactly at the slot capacity ``nps * ps``.
    Only real queries (index < true_len) are compared."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_attention as pa

    cap = nps * ps
    rng = np.random.RandomState(1)
    r = rows
    true_len = np.resize([t, max(1, t // 2 + 1), 1, t], r).astype(np.int32)
    pos0 = np.resize([cap - t, ps + 3, 0, 5 * ps - 1], r) \
        + np.arange(r) // 4 * (cap // 3 + 1)
    pos0 = np.minimum(pos0, cap - true_len).astype(np.int32)
    table = np.zeros((r, nps), np.int32)          # 0 = null page
    free = rng.permutation(np.arange(1, num_pages))
    used = 0
    for i in range(r):
        n = -(-int(pos0[i] + true_len[i]) // ps)
        table[i, :n] = free[used:used + n]
        used += n
    check(used <= num_pages - 1, "ragged check: pool too small")
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kq, (r, t, nh, hd), jnp.float32).astype(
        jnp.bfloat16)
    shape = (num_pages, ps, nh, hd)
    if int8:
        k_pool = jax.random.randint(kk, shape, -127, 128, jnp.int8)
        v_pool = jax.random.randint(kv, shape, -127, 128, jnp.int8)
        sc = rng.uniform(0.5, 1.5, (2, num_pages, nh)) / 64.0
        sc[:, 0] = 0.0                              # the null page's scale
        scales = dict(k_scale=jnp.asarray(sc[0], jnp.float32),
                      v_scale=jnp.asarray(sc[1], jnp.float32))
    else:
        k_pool = jax.random.normal(kk, shape, jnp.float32).astype(
            jnp.bfloat16)
        v_pool = jax.random.normal(kv, shape, jnp.float32).astype(
            jnp.bfloat16)
        scales = {}
    meta = (jnp.asarray(table), jnp.asarray(pos0), jnp.asarray(true_len))

    def kernel(q_, k_, v_):
        return pa.ragged_paged_attention(q_, k_, v_, *meta, impl="pallas",
                                         **scales)

    _mosaic_check(kernel, (q, k_pool, v_pool), pa._interpret(),
                  f"ragged T={t} int8={int8}")
    out = jax.jit(kernel)(q, k_pool, v_pool)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda q_, k_, v_: pa.ragged_paged_attention(
            q_, k_, v_, *meta, impl="xla", **scales))(
                q.astype(jnp.float32),
                k_pool if int8 else k_pool.astype(jnp.float32),
                v_pool if int8 else v_pool.astype(jnp.float32))
    real = np.arange(t)[None, :] < true_len[:, None]          # [r, t]
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    check(np.isfinite(out[real]).all(), "ragged kernel output not finite")
    err = _nerr(out[real], ref[real])
    check(err <= TOL_RAGGED,
          f"ragged T={t} int8={int8}: error {err:.3e} > {TOL_RAGGED}")
    return err


#: (rows, pages a slot, (T, int8) of each check): the serving cells' row
#: shapes. gpt3-1.3b-serve: 12 decode rows and a chunk of 32 over 128 pages
#: a slot; ouro-2.6b-serve: 10 rows over 32
RAGGED_ROWS = ((12, 128, ((1, False), (32, False), (1, True), (32, True))),
               (10, 32, ((1, False),)))


def phase_kernels(flash_shape, page: int, heads: int, head_dim: int) -> None:
    import jax.numpy as jnp

    t0 = time.perf_counter()
    errs = check_flash(*flash_shape, jnp.bfloat16)
    say("kernels", f"flash fwd+bwd {flash_shape} bf16 vs mha_reference: " +
        " ".join(f"{k}={v:.2e}" for k, v in errs.items()) +
        f" (tol {TOL_FLASH_FWD}/{TOL_FLASH_BWD})")
    for rows, nps, kinds in RAGGED_ROWS:
        pool_shape = (rows * nps + 1, page, heads, head_dim)
        for t, int8 in kinds:
            err = check_ragged(*pool_shape, nps, t, int8, rows=rows)
            say("kernels", f"ragged pallas vs xla pools={pool_shape} "
                f"{rows} rows {'int8+scales' if int8 else 'bf16'} T={t}: "
                f"err={err:.2e} (tol {TOL_RAGGED})")
    say("kernels", f"{time.perf_counter() - t0:.1f} s, "
        f"peak so far {_gb(peak_bytes())}")


def check_dropless(t: int, h: int, f: int, e: int, k: int, dtype,
                   forced: bool) -> dict:
    """``dropless_moe`` forward and gradients on ``t`` tokens against the
    float32 reference on the same (dtype-rounded) seeded inputs.
    ``forced``: the tokens share a direction that the router's column 0
    is aligned with, so expert 0 is nearly every token's choice.
    ``path`` in the result is what the layer counted for its grouped
    products while it was traced: ``pallas`` or ``xla``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.moe import dropless_moe
    from paddle_tpu.models import olmoe_reference as ref
    from paddle_tpu.profiler import metrics

    def counted():
        reg = metrics.registry()
        return {p: reg.counter("moe/grouped_matmul_calls{path=%s}" % p).value
                for p in ("pallas", "xla")}

    before = counted()

    ks = jax.random.split(jax.random.PRNGKey(1 + forced), 6)
    x = jax.random.normal(ks[0], (t, h), jnp.float32)
    router = jax.random.normal(ks[1], (h, e), jnp.float32) * 0.02
    if forced:
        x = x + 1.0
        router = router.at[:, 0].add(4.0 / h)
    args = [a.astype(dtype) for a in (
        x, router, jax.random.normal(ks[2], (e, h, f), jnp.float32) * 0.02,
        jax.random.normal(ks[3], (e, h, f), jnp.float32) * 0.02,
        jax.random.normal(ks[4], (e, f, h), jnp.float32) * 0.02)]
    w = jax.random.normal(ks[5], (t, h), jnp.float32)

    def program(*a):
        return dropless_moe(*a, top_k=k)

    def reference(x_, gate, w_gate, w_up, w_down):
        return ref.moe(x_, {"mlp.gate": gate, "mlp.w_gate": w_gate,
                            "mlp.w_up": w_up, "mlp.w_down": w_down}, k)

    def loss_of(fn):
        def loss(*a):
            y, balance, z = fn(*a)[:3]
            return jnp.sum(y.astype(jnp.float32) * w) + balance + z
        return loss

    out = jax.jit(program)(*args)
    grads = jax.jit(jax.grad(loss_of(program), range(5)))(*args)
    f32 = [a.astype(jnp.float32) for a in args]
    # the reference compiles one expert and loops: jitted as a whole, its
    # 64 experts unroll into a program that takes minutes to compile
    with jax.default_matmul_precision("highest"):
        want = reference(*f32)
        want_g = jax.grad(loss_of(reference), range(5))(*f32)
    jax.block_until_ready((out, grads, want, want_g))
    rows = np.asarray(out[3])
    load = float(rows.max()) * e / (t * k)
    errs = {"y": _nerr(out[0], want[0]),
            "balance": abs(float(out[1]) - float(want[1])) / float(want[1]),
            "z": abs(float(out[2]) - float(want[2])) / float(want[2])}
    errs.update({n: _nerr(g, wg) for n, g, wg in zip(
        ("dx", "drouter", "dw_gate", "dw_up", "dw_down"), grads, want_g)})
    check(np.isfinite(list(errs.values())).all(),
          f"experts non-finite {errs}")
    check(int(rows.sum()) == t * k,
          f"experts: {t * k - int(rows.sum())} assignments dropped")
    check(load >= 4.0 if forced else load < 2.0,
          f"experts: load max/mean {load} with forced={forced}")
    check(max(errs["y"], errs["balance"], errs["z"]) <= TOL_EXPERTS_FWD,
          f"experts fwd error {errs}")
    check(max(errs[n] for n in ("dx", "drouter", "dw_gate", "dw_up",
                                "dw_down")) <= TOL_EXPERTS_BWD,
          f"experts bwd error {errs}")
    paths = [p for p, n in counted().items() if n > before[p]]
    check(len(paths) == 1, f"experts: grouped products counted on {paths}")
    return {**errs, "load": load, "path": paths[0]}


def phase_experts(t: int, h: int, f: int, e: int, k: int) -> None:
    import jax.numpy as jnp

    t0 = time.perf_counter()
    for forced in (False, True):
        tiles0 = gmm_tiles()
        r = check_dropless(t, h, f, e, k, jnp.bfloat16, forced)
        path = r.pop("path")
        check(path == "pallas", f"experts: the grouped products took the "
              f"{path} path on one TPU device at widths that tile")
        tiles = check_gmm_tiles(tiles0, h, f, "experts")
        say("experts", f"dropless_moe T={t} h={h} {e} experts of {f}, "
            f"top-{k}, {'one expert forced' if forced else 'balanced'}, "
            f"grouped products through {path} in tiles {sorted(tiles)}: "
            f"load max/mean {r.pop('load'):.2f}, dropped 0; vs float32 "
            "reference " + " ".join(f"{n}={v:.2e}" for n, v in r.items())
            + f" (tol {TOL_EXPERTS_FWD}/{TOL_EXPERTS_BWD})")
    say("experts", f"{time.perf_counter() - t0:.1f} s, "
        f"peak so far {_gb(peak_bytes())}")


# ---------------------------------------------------------------------------
# phase 1d: the fused head's loss and gradients
# ---------------------------------------------------------------------------
def check_head(b: int, s: int, h: int, v: int, w_is_vh: bool, dtype,
               mesh=None) -> dict:
    """The fused lm-head + cross-entropy on ``[b, s, h]`` states and a
    ``v``-word vocabulary, next-token labels, chunks of 256 as the models
    call it: loss, ``dx`` and ``dW`` against autodifferentiation of the
    plain float32 reference at highest precision on the same
    (dtype-rounded) inputs, a row at a time (its ``[b, s, v]`` float32
    logits are what the head exists not to hold). With ``mesh`` (axes
    ``pp`` and ``tp``) the head runs where the pipeline's ``head_fn`` does:
    inside a region manual over ``pp`` alone, the vocabulary sharded over
    ``tp`` (every stage on the whole of ``x`` and the last stage's loss
    kept, the form ``pipeline_apply`` takes where the micro-batches do not
    divide among the stages)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.fused_ce import (IGNORE,
                                         fused_linear_cross_entropy_fn,
                                         shifted_labels)
    from paddle_tpu.profiler import metrics

    def traced():
        reg = metrics.registry()
        return {r: reg.counter("head/fused_ce_traces{rule=%s}" % r).value
                for r in ("grad_in_forward", "loss_only")}

    ks = jax.random.split(jax.random.PRNGKey(v + h), 3)
    x = jax.random.normal(ks[0], (b, s, h), jnp.float32).astype(dtype)
    w = (0.02 * jax.random.normal(ks[1], (v, h) if w_is_vh else (h, v),
                                  jnp.float32)).astype(dtype)
    labels = shifted_labels(jax.random.randint(ks[2], (b, s), 0, v))
    n = int(jnp.sum(labels != IGNORE))

    def head(x_, w_, labels_):
        return fused_linear_cross_entropy_fn(x_, w_, labels_, chunk=256,
                                             transpose_w=not w_is_vh)

    program, args = head, (x, w, labels)
    if mesh is not None:
        last = mesh.shape["pp"] - 1

        def program(x_, w_, labels_):
            @jax.shard_map(mesh=mesh, in_specs=(P(), P(), P()),
                           out_specs=P(), check_vma=False,
                           axis_names=frozenset({"pp"}))
            def region(x_, w_, labels_):
                out = head(x_, w_, labels_)
                return jax.lax.psum(jnp.where(
                    jax.lax.axis_index("pp") == last, out, 0.0), "pp")
            return region(x_, w_, labels_)

        w_spec = P("tp", None) if w_is_vh else P(None, "tp")
        args = tuple(jax.device_put(a, NamedSharding(mesh, spec))
                     for a, spec in zip(args, (P(), w_spec, P())))

    @jax.jit
    def reference_row(x_, w_, labels_):
        def row_loss(x_, w_):
            logits = jnp.einsum("sh,vh->sv" if w_is_vh else "sh,hv->sv",
                                x_, w_)
            logp = jax.nn.log_softmax(logits, axis=-1)
            gold = jnp.take_along_axis(
                logp, jnp.clip(labels_, 0)[:, None], axis=-1)[:, 0]
            return -jnp.sum(jnp.where(labels_ != IGNORE, gold, 0.0)) / n
        return jax.value_and_grad(row_loss, (0, 1))(x_, w_)

    before = traced()
    loss, (dx, dw) = jax.jit(jax.value_and_grad(program, (0, 1)))(*args)
    after = traced()
    check(after["grad_in_forward"] == before["grad_in_forward"] + 1
          and after["loss_only"] == before["loss_only"],
          f"head: the differentiated program traced {after} after {before}, "
          "not the rule that makes the gradients in the forward pass once")
    w32 = w.astype(jnp.float32)
    want, want_dx, want_dw = 0.0, [], jnp.zeros(w.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i in range(b):
            row, (row_dx, row_dw) = reference_row(
                x[i].astype(jnp.float32), w32, labels[i])
            want, want_dw = want + float(row), want_dw + row_dw
            want_dx.append(np.asarray(row_dx))
    errs = {"loss": abs(float(loss) - want) / abs(want),
            "dx": _nerr(dx, np.stack(want_dx)), "dW": _nerr(dw, want_dw)}
    check(dx.dtype == x.dtype and dw.dtype == w.dtype,
          f"head: gradients in {dx.dtype}, {dw.dtype}")
    check(np.isfinite(list(errs.values())).all(), f"head non-finite {errs}")
    check(errs["loss"] <= TOL_HEAD_LOSS, f"head loss error {errs}")
    for name in ("dx", "dW"):
        check(errs[name] <= TOL_HEAD_GRAD, f"head {name} error {errs}")
    return errs


def _head_errs(errs: dict) -> str:
    return ("vs float32 autodiff: "
            + " ".join(f"{k}={e:.2e}" for k, e in errs.items())
            + f" (tol {TOL_HEAD_LOSS}/{TOL_HEAD_GRAD})")


def phase_head(shapes) -> None:
    import jax.numpy as jnp

    t0 = time.perf_counter()
    for b, s, h, v, w_is_vh in shapes:
        errs = check_head(b, s, h, v, w_is_vh, jnp.bfloat16)
        say("head", f"fused lm-head + CE x[{b}, {s}, {h}] "
            f"W[{'V, H' if w_is_vh else 'H, V'}] V={v} bf16, gradients made "
            "in the forward pass, " + _head_errs(errs))
        release_device_memory()
    say("head", f"{time.perf_counter() - t0:.1f} s, "
        f"peak so far {_gb(peak_bytes())}")


# ---------------------------------------------------------------------------
# phase 2: serve
# ---------------------------------------------------------------------------
def check_submits(submits, limit_ms: float) -> None:
    """``submit()`` asks nothing of the serving device, so it does not wait
    for the ticks in flight (on the device's queue it took two to three of
    them). ``submits``: (ticks in flight, host ms) of each call."""
    behind = [ms for inflight, ms in submits if inflight]
    check(all(ms <= limit_ms for ms in behind),
          f"submit() waited behind the ticks in flight: {behind} ms, "
          f"each may take {limit_ms}")


def attn_calls(counter: str = "serving/attn_calls") -> dict:
    """``serving/attn_calls{path=}``: attention calls by the path they
    took, counted on the host while a tick is traced (``counter``:
    ``serving/latent_attn_calls`` for the selected latent attention's)."""
    from paddle_tpu.profiler import registry

    return {p: registry().counter("%s{path=%s}" % (counter, p)).value
            for p in ("pallas", "xla")}


def gmm_tiles() -> dict:
    """``moe/grouped_matmul_tiles{tile=}``: the grouped products that took
    the kernel, by the tile (rows, contraction, columns a grid step) they
    walk a group's matrix in; counted on the host while a layer is traced."""
    from paddle_tpu.profiler import registry

    head = "moe/grouped_matmul_tiles{tile="
    return {tuple(int(x) for x in name[len(head):-1].split("x")): m["value"]
            for name, m in registry().snapshot().items()
            if name.startswith(head)}


def check_gmm_tiles(before: dict, h: int, f: int, what: str) -> dict:
    """The expert layers of ``[h, f]`` matrices traced since ``before``
    (``gmm_tiles()`` then) counted each of their grouped products that took
    the kernel (none where they went by ``ragged_dot``: off the chip, or
    widths that do not tile) under one tile, the rule's, and that tile
    holds the whole contraction: one grid step a visit of a group."""
    from paddle_tpu.ops.grouped_matmul import kernel_path, tile_for

    took = {t: n - before.get(t, 0) for t, n in gmm_tiles().items()
            if n > before.get(t, 0)}
    want = {kn: tile_for(128, *kn) for kn in ((h, f), (f, h))
            if kernel_path(128, *kn) == "pallas"}
    check(set(took) == set(want.values())
          and all(t[1] == k for (k, _), t in want.items()),
          f"{what}: the grouped products of [{h}, {f}] experts counted the "
          f"tiles {took}, not {sorted(want.values())} with the whole "
          "contraction a step (moe/grouped_matmul_tiles)")
    return took


def check_attn_path(before: dict) -> str:
    """The ticks traced since ``before`` took the platform's attention and
    no other: on the chip the ragged kernel (the CPU's rehearsal runs the
    XLA spelling)."""
    import jax

    want = "xla" if jax.default_backend() == "cpu" else "pallas"
    took = {p: n - before[p] for p, n in attn_calls().items()}
    check(0 < took[want] == sum(took.values()),
          f"the engine's tick took its attention by {took}, not by {want}")
    return want


def check_tick_memory(temp: float, alias: float, pool_bytes) -> None:
    """The pools stay where they are (ROADMAP S3): the compiled tick's
    temporaries (gauge ``serving/tick_temp_bytes``) are under ONE pool's
    bytes and it aliases all of the donated pools
    (``serving/tick_alias_bytes``). A pool-sized temporary is a copy of a
    whole pool every tick. ``pool_bytes``: the bytes of each pool array."""
    check(0 <= temp < max(pool_bytes),
          f"the compiled tick holds {_gb(temp)} of temporaries, a whole "
          f"page pool ({_gb(max(pool_bytes))}) or more: the pools are copied")
    check(alias >= sum(pool_bytes),
          f"the compiled tick aliases {_gb(alias)}, not all of the donated "
          f"pools ({_gb(sum(pool_bytes))})")


def phase_serve(cfg, num_slots: int, page_size: int, requests,
                submit_limit_ms: float = SUBMIT_LIMIT_MS) -> dict:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import GPT
    from paddle_tpu.profiler import recompile, registry
    from paddle_tpu.serving import ServingConfig, ServingEngine

    reg = registry()

    def counter(name):
        return reg.counter("serving/" + name).value

    t_setup = time.perf_counter()
    attn_before = attn_calls()
    paddle.seed(0)
    net = GPT(cfg)
    net.eval()
    net.bfloat16()
    eng = ServingEngine(net, ServingConfig(num_slots=num_slots,
                                           page_size=page_size))
    on_default_platform((eng._stacked, eng._other, eng.pool.k, eng.pool.v),
                        "serving state")
    setup_s = time.perf_counter() - t_setup

    rng = np.random.RandomState(7)
    shared = rng.randint(0, cfg.vocab_size,
                         max(r[3] for r in requests)).astype(np.int32)
    prompts = []
    for _, n, _, pre in requests:
        p = rng.randint(0, cfg.vocab_size, n).astype(np.int32)
        p[:pre] = shared[:pre]
        prompts.append(p)

    base = {n: counter(n) for n in ("ticks", "prefix_hit_tokens",
                                    "tokens_generated", "prefill_chunks")}
    pending = sorted(range(len(requests)), key=lambda i: requests[i][0])
    rids = {}
    submits = []        # (ticks in flight, host ms) of each submit()
    kinds = {"mixed": 0, "decode_only": 0, "prefill_only": 0}
    tick, first_tick_s = 0, None
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t_run = time.perf_counter()
        while pending or not eng.idle():
            while pending and requests[pending[0]][0] <= tick:
                i = pending.pop(0)
                inflight = len(eng._inflight)
                t_submit = time.perf_counter()
                rids[i] = eng.submit(prompts[i], requests[i][2])
                submits.append(
                    (inflight, (time.perf_counter() - t_submit) * 1e3))
            if eng.step():
                dec = reg.gauge("serving/mixed_rows_decode").value
                pre = reg.gauge("serving/mixed_rows_prefill").value
                kinds["mixed" if dec and pre else
                      "decode_only" if dec else "prefill_only"] += 1
            else:
                eng.drain(0)
            if first_tick_s is None:
                # the one tick program's compile sits in this first sync
                eng.drain(0)
                first_tick_s = time.perf_counter() - t_run
            tick = requests[pending[0]][0] if pending and eng.idle() \
                else tick + 1
            check(tick < 100_000, "the engine stopped making progress")
        results = eng.run()              # nothing is left: the results
        jax.block_until_ready((eng.pool.k, eng.pool.v))
        run_s = time.perf_counter() - t_run
    donation = [str(w.message) for w in caught
                if "donated buffers were not usable" in str(w.message)]
    check(not donation, f"the tick's pool donation was declined: "
          f"{donation[:1]}")
    tick_temp = reg.gauge("serving/tick_temp_bytes").value
    tick_alias = reg.gauge("serving/tick_alias_bytes").value
    pool_bytes = [a.nbytes for a in eng.pool.pools.arrays().values()]
    if jax.default_backend() != "cpu":
        # the CPU's XLA (the rehearsal in tests/test_chip_smoke.py) widens
        # a whole bf16 pool to float32 around every scatter; what the CPU
        # can say, it says on float32 pools in tests/test_pools.py
        check_tick_memory(tick_temp, tick_alias, pool_bytes)
    attn_path = check_attn_path(attn_before)

    for i, (_, _, want, _) in enumerate(requests):
        out = results.get(rids[i])
        check(out is not None, f"request {i} did not finish")
        check(len(out) == want, f"request {i}: {len(out)} tokens, "
              f"asked for {want}")
        check(((out >= 0) & (out < cfg.vocab_size)).all(),
              f"request {i}: token id out of range")
    hits = counter("prefix_hit_tokens") - base["prefix_hit_tokens"]
    check(hits > 0, "serving/prefix_hit_tokens did not move")
    check(len(requests) <= num_slots or kinds["mixed"] > 0,
          f"no mixed tick happened: {kinds}")
    check(kinds["decode_only"] > 0, f"no decode-only tick happened: {kinds}")
    traces = recompile.trace_counts()
    check(len(eng.compiled_sites) == 1
          and traces.get(eng.compiled_sites[0]) == 1,
          f"the tick is not one site traced once: "
          f"{[(s, traces.get(s)) for s in eng.compiled_sites]}")
    on_default_platform((eng.pool.k, eng.pool.v), "page pools after the run")
    check_submits(submits, submit_limit_ms)

    # one request's greedy tokens against the dense generate() path
    probe = min(range(len(requests)), key=lambda i: requests[i][1])
    t_dense = time.perf_counter()
    dense, _ = net.generate(jnp.asarray(prompts[probe])[None],
                            max_new_tokens=requests[probe][2])
    dense = np.asarray(dense._value)[0]
    dense_s = time.perf_counter() - t_dense
    paged = results[rids[probe]]
    match = int(np.sum(dense == paged))
    check(dense[0] == paged[0], f"first greedy token differs: dense "
          f"{dense[0]} vs paged {paged[0]}")

    out = {"setup_s": setup_s, "first_tick_s": first_tick_s, "run_s": run_s,
           "ticks": int(counter("ticks") - base["ticks"]),
           "tokens": int(counter("tokens_generated")
                         - base["tokens_generated"]),
           "prefill_chunks": int(counter("prefill_chunks")
                                 - base["prefill_chunks"]),
           "prefix_hit_tokens": int(hits), "tick_kinds": kinds,
           "dense_match": f"{match}/{len(paged)}", "dense_s": dense_s,
           "submits": submits, "peak_bytes": peak_bytes(),
           "tick_temp_bytes": tick_temp, "tick_alias_bytes": tick_alias}
    say("serve", f"{len(requests)} requests on {num_slots} slots, all "
        f"finished; set-up {setup_s:.1f} s, first tick (compile) "
        f"{first_tick_s:.1f} s, run {run_s:.1f} s wall to "
        f"block_until_ready")
    say("serve", f"ticks={out['ticks']} {kinds} chunks="
        f"{out['prefill_chunks']} tokens={out['tokens']} "
        f"prefix_hit_tokens={out['prefix_hit_tokens']} sites="
        f"{eng.compiled_sites} traced once, attention through "
        f"{attn_path}, no donation warning")
    say("serve", f"the compiled tick: serving/tick_temp_bytes "
        f"{tick_temp:.0f} ({_gb(tick_temp)}), serving/tick_alias_bytes "
        f"{tick_alias:.0f} ({_gb(tick_alias)}); the pools "
        f"{' + '.join(_gb(b) for b in pool_bytes)}")
    say("serve", "submit() host ms (ticks in flight): "
        + ", ".join(f"{ms:.3f} ({inflight})" for inflight, ms in submits))
    say("serve", f"greedy paged vs dense generate() (prompt "
        f"{requests[probe][1]}): {out['dense_match']} tokens equal, first "
        f"equal; dense compile+run {dense_s:.1f} s; peak so far "
        f"{_gb(out['peak_bytes'])}")
    return out


def phase_serve_looped(cfg, num_slots: int, page_size: int,
                       pages_per_slot: int, requests=LOOP_REQUESTS) -> dict:
    """One pass of a looped model (``cfg.loop_steps`` > 1: sandwich norms,
    RoPE, SwiGLU, an exit gate) through the engine: a ``LazyGuard`` model's
    weights are drawn on the device once, into the engine's stacks
    (``serving/weights_bytes`` is one copy in the model's type), the tick over ``loop_steps * num_layers``
    cache layers holds no pool-sized temporary, and what it emitted is the
    float32 reference's (models/ouro_reference.py), tokens and exit
    steps."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import GPT
    from paddle_tpu.models import ouro_reference as ref
    from paddle_tpu.profiler import registry
    from paddle_tpu.serving import ServingConfig, ServingEngine

    reg = registry()
    t_setup = time.perf_counter()
    attn_before = attn_calls()
    paddle.seed(0)
    with paddle.LazyGuard():
        net = GPT(cfg)
    net.eval()
    net.bfloat16()
    eng = ServingEngine(net, ServingConfig(
        num_slots=num_slots, page_size=page_size,
        pages_per_slot=pages_per_slot))
    stacked, other = eng.served_weights()
    on_default_platform((stacked, other, eng.pool.k), "looped serving state")
    setup_s = time.perf_counter() - t_setup
    weights = reg.gauge("serving/weights_bytes").value
    check(weights == 2 * cfg.num_params(),
          f"serving/weights_bytes {weights:.0f} is not one bf16 copy of "
          f"{cfg.num_params()} parameters")
    layers = cfg.loop_steps * cfg.num_layers
    check(reg.gauge("serving/cache_layers").value == layers
          == eng.pool.k.shape[0], f"the pools are not {layers} layers deep")

    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n, _ in requests]
    rids = [eng.submit(p, new) for p, (_, new) in zip(prompts, requests)]
    t_run = time.perf_counter()
    results = eng.run()
    jax.block_until_ready(eng.pool.k)
    run_s = time.perf_counter() - t_run
    tick_temp = reg.gauge("serving/tick_temp_bytes").value
    tick_alias = reg.gauge("serving/tick_alias_bytes").value
    pool_bytes = [a.nbytes for a in eng.pool.pools.arrays().values()]
    if jax.default_backend() != "cpu":      # as in phase_serve
        check_tick_memory(tick_temp, tick_alias, pool_bytes)
    check_attn_path(attn_before)

    def layer_weights():
        for i in range(cfg.num_layers):
            yield {k: v[i] for k, v in stacked.items()}

    worst = gap = 0.0
    for rid, prompt in zip(rids, prompts):
        out = results[rid]
        seq = np.concatenate([prompt, out[:-1]])[None]
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        targets = np.zeros(seq.shape, np.int32)
        mask = np.zeros(seq.shape, bool)
        targets[0, at], mask[0, at] = out, True
        got = ref.forward(layer_weights, other, seq, cfg.num_heads,
                          cfg.loop_steps, cfg.exit_threshold,
                          cfg.layer_norm_eps, cfg.rope_theta)
        worst = max(worst, float(
            ref.shortfall(got["state"], other, targets, mask).max()))
        gap = max(gap, abs(eng.exit_steps(rid)[0] - float(
            np.asarray(got["expected"])[0, at].mean())))
    check(worst <= TOL_LOOP_SHORTFALL,
          f"an emitted token's logit lies {worst:.4f} below the float32 "
          f"reference's largest, allowed {TOL_LOOP_SHORTFALL}")
    check(gap <= TOL_LOOP_EXIT,
          f"a request's mean expected exit step is {gap:.5f} from the "
          f"reference's, allowed {TOL_LOOP_EXIT}")
    say("serve", f"looped pass ({cfg.loop_steps} steps x {cfg.num_layers} "
        f"layers = {layers} cache layers): set-up {setup_s:.1f} s, run "
        f"{run_s:.1f} s; serving/weights_bytes {weights:.0f} "
        f"({_gb(weights)}, one bf16 copy); tick_temp_bytes "
        f"{tick_temp:.0f} ({_gb(tick_temp)}) beside pools of "
        f"{' + '.join(_gb(b) for b in pool_bytes)}; worst shortfall "
        f"{worst:.4f} (allowed {TOL_LOOP_SHORTFALL}), exit step within "
        f"{gap:.5f} (allowed {TOL_LOOP_EXIT}), mean expected exit step "
        f"{eng.exit_steps()[0]:.3f}")
    return {"worst": worst, "exit_gap": gap, "weights_bytes": weights,
            "tick_temp_bytes": tick_temp, "tick_alias_bytes": tick_alias,
            "cache_layers": layers}


def check_latent_ops(rows: int, t: int, heads: int, width: int, c: int,
                     page: int, pages: int, topk: int, window: int,
                     dtype) -> dict:
    """``ops/latent_attention``'s latent-pool reads at the given row shape
    (``rows`` rows of ``t`` queries over ``pages`` pages of ``page``
    tokens, half of them live) against float32 spellings over the whole
    rows: the indexer's scores, the selection (threshold against
    ``top_k``), attention over the selection and over the window."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import latent_attention as pa

    rng = np.random.default_rng(5)
    cap = pages * page
    pool = jnp.asarray(rng.normal(size=(1, rows * pages + 1, width, page)),
                       dtype)
    table = 1 + np.arange(rows * pages, dtype=np.int32).reshape(rows, pages)
    pos0 = (cap // 2 + np.arange(rows) * 3 - t).astype(np.int32)
    true_len = np.full(rows, t, np.int32)
    q = jnp.asarray(rng.normal(size=(rows, t, heads, width)) * .3, dtype)
    q_i = jnp.asarray(rng.normal(size=(rows, t, 4, width)), dtype)
    w_i = jnp.asarray(rng.normal(size=(rows, t, 4)), jnp.float32)
    flat = jnp.swapaxes(pool[0, table], 2, 3).reshape(
        rows, cap, width).astype(jnp.float32)               # [R, S, W]
    qpos = pos0[:, None] + np.arange(t)[None]
    seen = np.arange(cap)[None, None] <= qpos[..., None]    # [R, T, S]

    score = jax.jit(pa.index_scores, static_argnums=3)(
        q_i, w_i, pool, 0, table, pos0, true_len)
    want = jnp.einsum("rtj,rtjs->rts", w_i, jax.nn.relu(jnp.einsum(
        "rtjd,rsd->rtjs", q_i.astype(jnp.float32), flat)))
    errs = {"index": _nerr(jnp.where(seen, score, 0.0),
                           jnp.where(seen, want, 0.0))}
    check(bool(jnp.all(jnp.isneginf(jnp.where(seen, -jnp.inf, score)))),
          "an invisible position has a score")
    keys, thr, ties = pa.select_threshold(score.reshape(rows * t, cap), topk)
    mask = pa.selection_mask(keys, thr, ties).reshape(rows, t, cap) & seen
    idx, valid = pa.select_topk(score.reshape(rows * t, cap), topk)
    mine = np.asarray(mask.reshape(rows * t, cap))
    same = all(set(np.flatnonzero(mine[i])) == set(
        np.asarray(idx[i])[np.asarray(valid[i])]) for i in range(0, rows * t,
                                                               max(t // 4, 1)))
    check(same, "the threshold's selection is not top_k's")

    def dense(keep, scale):
        sc = jnp.einsum("rtnc,rsc->rtns", q.astype(jnp.float32), flat) \
            * scale
        pr = jax.nn.softmax(jnp.where(keep[:, :, None], sc, -jnp.inf), -1)
        return jnp.einsum("rtns,rsc->rtnc", pr, flat[..., :c])

    def selected(impl):
        return jax.jit(pa.selected_latent_attention,
                       static_argnums=(2, 9, 10, 11))(
            q, pool, 0, table, pos0, true_len, keys.reshape(rows, t, cap),
            thr.reshape(rows, t), ties.reshape(rows, t), c, 0.06, impl)

    # the spelling the platform and this shape pick (on the chip, at the
    # cell's row shapes, the Pallas kernel), then the other one
    path = pa.latent_attention_path(q, pool, c)
    other = "xla" if path == "pallas" else "pallas"
    want = dense(mask, 0.06)
    errs["selected"] = _nerr(selected(None), want)
    errs["selected_" + other] = _nerr(selected(other), want)
    got, _ = jax.jit(pa.window_latent_attention, static_argnums=(2, 6, 7, 8))(
        q, pool, 0, table, pos0, true_len, window, c, 0.06)
    inside = seen & (np.arange(cap)[None, None] > qpos[..., None] - window)
    errs["window"] = _nerr(got, dense(jnp.asarray(inside), 0.06))
    for name, err in errs.items():
        check(err <= TOL_LATENT_OPS,
              f"latent {name} read off by {err:.2e}, allowed {TOL_LATENT_OPS}")
    return {**errs, "path": path}


def phase_latent(cfg, num_slots: int, page_size: int, pages_per_slot: int,
                 ops_shapes, requests=LATENT_REQUESTS) -> dict:
    """The latent-attention pass (models/dots3.py): the latent pools' read
    sides against plain spellings at each of ``ops_shapes`` (the selected
    attention through both of its spellings; the one these shapes pick
    must be the platform's: on the chip the kernel), then a small model
    through the engine (a ``LazyGuard`` model drawn on the device, latent,
    indexer-key and windowed pools, the held experts inside the tick): what
    it emitted is the float32 reference's (models/dots3_reference.py), the
    sets its indexer selected are the reference's but for near-ties, and the
    windowed layers' pages behind the window went back."""
    import dataclasses as dc

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import dots3_reference as ref
    from paddle_tpu.models.dots3 import Dots3
    from paddle_tpu.profiler import registry
    from paddle_tpu.serving import ServingConfig, ServingEngine

    from paddle_tpu.ops.paged_attention import resolve_impl

    errs = {}
    for shape in ops_shapes:
        got = check_latent_ops(*shape)
        path = got.pop("path")
        rows, t, heads, width = shape[:4]
        say("latent", f"{rows} rows of {t} queries, {heads} heads over "
            f"{width}: reads against float32 spellings: " + ", ".join(
                f"{k} {v:.2e}" for k, v in got.items())
            + f" (allowed {TOL_LATENT_OPS}); the selected attention's own "
            f"path here: {path}")
        check(path == resolve_impl(None),
              f"the selected latent attention of {heads} heads over {width} "
              f"went by {path} where the platform's is {resolve_impl(None)}")
        errs = {k: max(v, errs.get(k, 0.0)) for k, v in got.items()}
    reg = registry()
    calls0 = attn_calls("serving/latent_attn_calls")
    tiles0 = gmm_tiles()
    freed0 = reg.counter("serving/window_pages_freed").value
    paddle.seed(0)
    with paddle.LazyGuard():
        net = Dots3(cfg)
    net.eval()
    net.bfloat16()
    eng = ServingEngine(net, ServingConfig(
        num_slots=num_slots, page_size=page_size,
        pages_per_slot=pages_per_slot, prefix_cache=False))
    layers, other = eng.served_weights()
    on_default_platform((layers, other, eng.pool.pools),
                        "latent serving state")
    weights = reg.gauge("serving/weights_bytes").value
    check(weights == 2 * cfg.num_params(),
          f"serving/weights_bytes {weights:.0f} is not one bf16 copy of "
          f"{cfg.num_params()} parameters")
    rng = np.random.RandomState(13)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n, _ in requests]
    rids = [eng.submit(p, new) for p, (_, new) in zip(prompts, requests)]
    results = eng.run()
    jax.block_until_ready(eng.pool.pools)
    freed = reg.counter("serving/window_pages_freed").value - freed0
    check(freed > 0, "no page of a windowed layer went back")
    check(eng.pool.check_consistency() == [], "the pools' books disagree")
    config = dc.asdict(cfg)

    def layer_weights():
        for i, kind in enumerate(cfg.layer_types):
            yield kind, cfg.is_moe(i), layers[f"layer{i}"]

    differ, shorts = 0.0, []
    for rid, prompt in zip(rids, prompts):
        out = results[rid]
        seq = np.concatenate([prompt, out[:-1]])
        got = ref.forward(layer_weights(), other, seq, config, cfg.held)
        at = np.arange(len(prompt) - 1, len(seq))
        shorts.append(ref.shortfall(np.asarray(got["state"])[at], other,
                                    out)[0])
        for pos, sets in eng.tick_record.selected_sets(rid):
            for layer, mine in enumerate(sets):
                theirs = np.asarray(got["selected"][layer][pos])
                a, b = set(mine.tolist()), set(theirs[theirs >= 0].tolist())
                differ = max(differ, len(a ^ b) / max(2 * len(b), 1))
    shorts = np.concatenate(shorts)
    worst, median = float(shorts.max()), float(np.median(shorts))
    check(median <= TOL_LATENT_SHORTFALL and worst <= TOL_LATENT_WORST,
          f"an emitted token's logit lies {median:.4f} below the float32 "
          f"reference's largest at the median (allowed "
          f"{TOL_LATENT_SHORTFALL}) and {worst:.4f} at the worst (allowed "
          f"{TOL_LATENT_WORST})")
    check(differ <= TOL_LATENT_SELECTED,
          f"a selected set differs from the reference's in {differ:.3f} of "
          f"it, allowed {TOL_LATENT_SELECTED}")
    paths = {k: v["value"] for k, v in reg.snapshot().items()
             if k.startswith("moe/grouped_matmul_calls")}
    paths["tiles"] = check_gmm_tiles(
        tiles0, cfg.hidden_size, cfg.moe_intermediate_size, "latent")
    tick = {p: n - calls0[p] for p, n in
            attn_calls("serving/latent_attn_calls").items() if n > calls0[p]}
    check(tick, "no tick counted its selected latent attention")
    say("latent", f"the engine's ticks compiled their selected attention by "
        f"{tick} (serving/latent_attn_calls; the kernel where Mosaic tiles "
        f"the model's heads, latents and pages)")
    say("latent", f"{len(rids)} requests through latent, indexer-key and "
        f"windowed pools ({cfg.num_hidden_layers} layers): shortfall "
        f"{median:.4f} at the median (allowed {TOL_LATENT_SHORTFALL}), "
        f"{worst:.4f} at the worst (allowed {TOL_LATENT_WORST}), a selected "
        f"set "
        f"differs by at most {differ:.3f} (allowed {TOL_LATENT_SELECTED}), "
        f"{freed:.0f} windowed pages given back; weights {_gb(weights)}"
        f"; {paths}")
    return {"worst": worst, "median": median, "differ": differ,
            "freed": freed, "weights_bytes": weights, "tick_paths": tick,
            **errs}


def check_dense_latent(rows: int, t: int, heads: int, width: int, c: int,
                       page: int, pages: int, dtype) -> dict:
    """``ops/latent_attention.latent_attention`` at the given row shape
    (``rows`` rows of ``t`` queries over ``pages`` pages of ``page`` tokens,
    half of them live) through both spellings against a float32 softmax
    over the whole rows."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import latent_attention as pa

    rng = np.random.default_rng(6)
    cap = pages * page
    pool = jnp.asarray(rng.normal(size=(1, rows * pages + 1, width, page)),
                       dtype)
    table = 1 + np.arange(rows * pages, dtype=np.int32).reshape(rows, pages)
    pos0 = (cap // 2 + np.arange(rows) * 3 - t).astype(np.int32)
    true_len = np.full(rows, t, np.int32)
    q = jnp.asarray(rng.normal(size=(rows, t, heads, width)) * .3, dtype)
    flat = jnp.swapaxes(pool[0, table], 2, 3).reshape(
        rows, cap, width).astype(jnp.float32)               # [R, S, W]
    qpos = pos0[:, None] + np.arange(t)[None]
    seen = np.arange(cap)[None, None] <= qpos[..., None]    # [R, T, S]
    sc = jnp.einsum("rtnc,rsc->rtns", q.astype(jnp.float32), flat) * 0.06
    pr = jax.nn.softmax(jnp.where(seen[:, :, None], sc, -jnp.inf), -1)
    want = jnp.einsum("rtns,rsc->rtnc", pr, flat[..., :c])

    def attend(impl):
        return jax.jit(pa.latent_attention, static_argnums=(2, 6, 7, 8))(
            q, pool, 0, table, pos0, true_len, c, 0.06, impl)

    path = pa.latent_attention_path(q, pool, c)
    other = "xla" if path == "pallas" else "pallas"
    errs = {"dense": _nerr(attend(None), want),
            "dense_" + other: _nerr(attend(other), want)}
    for name, err in errs.items():
        check(err <= TOL_LATENT_OPS,
              f"latent {name} read off by {err:.2e}, allowed {TOL_LATENT_OPS}")
    return {**errs, "path": path}


def phase_dsv2(cfg, num_slots: int, page_size: int, pages_per_slot: int,
               ops_shapes, requests=LATENT_REQUESTS) -> dict:
    """DeepSeek-V2's pass (models/deepseek_v2.py): the dense latent
    attention through both of its spellings against a float32 softmax at
    each of ``ops_shapes`` (the one these shapes pick must be the
    platform's: on the chip the kernel), then a small model through the
    engine, two prefill chunks a tick (a ``LazyGuard`` model drawn on the
    device, latent pools alone, the held experts under the group limit
    inside the tick): what it emitted is the float32 reference's
    (models/deepseek_v2_reference.py)."""
    import dataclasses as dc

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import deepseek_v2_reference as ref
    from paddle_tpu.models.deepseek_v2 import DeepseekV2
    from paddle_tpu.ops.paged_attention import resolve_impl
    from paddle_tpu.profiler import registry
    from paddle_tpu.serving import ServingConfig, ServingEngine

    errs = {}
    for shape in ops_shapes:
        got = check_dense_latent(*shape)
        path = got.pop("path")
        rows, t, heads, width = shape[:4]
        say("dsv2", f"{rows} rows of {t} queries, {heads} heads over "
            f"{width}: dense latent attention against a float32 softmax: "
            + ", ".join(f"{k} {v:.2e}" for k, v in got.items())
            + f" (allowed {TOL_LATENT_OPS}); its own path here: {path}")
        check(path == resolve_impl(None),
              f"the dense latent attention of {heads} heads over {width} "
              f"went by {path} where the platform's is {resolve_impl(None)}")
        errs = {k: max(v, errs.get(k, 0.0)) for k, v in got.items()}
    reg = registry()
    counter = "serving/latent_attn_calls{path=%s,kind=dense}"
    calls0 = {p: reg.counter(counter % p).value for p in ("pallas", "xla")}
    tiles0 = gmm_tiles()
    paddle.seed(0)
    with paddle.LazyGuard():
        net = DeepseekV2(cfg)
    net.eval()
    net.bfloat16()
    eng = ServingEngine(net, ServingConfig(
        num_slots=num_slots, page_size=page_size,
        pages_per_slot=pages_per_slot, prefix_cache=False,
        prefill_chunks_per_tick=2))
    layers, other = eng.served_weights()
    on_default_platform((layers, other, eng.pool.pools), "dsv2 serving state")
    weights = reg.gauge("serving/weights_bytes").value
    check(weights == 2 * cfg.num_params(),
          f"serving/weights_bytes {weights:.0f} is not one bf16 copy of "
          f"{cfg.num_params()} parameters")
    check(eng.pool.pools.index_k.size == 0 and eng.pool.pools.window.size
          == 0, "pools were made for an indexer or a window it has not")
    rng = np.random.RandomState(13)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n, _ in requests]
    rids = [eng.submit(p, new) for p, (_, new) in zip(prompts, requests)]
    results = eng.run()
    jax.block_until_ready(eng.pool.pools)
    check(eng.pool.check_consistency() == [], "the pools' books disagree")
    config = dc.asdict(cfg)
    shorts = []
    for rid, prompt in zip(rids, prompts):
        out = results[rid]
        seq = np.concatenate([prompt, out[:-1]])
        got = ref.forward(((cfg.is_moe(i), layers[f"layer{i}"])
                           for i in range(cfg.num_hidden_layers)), other,
                          seq, config, cfg.held)
        at = np.arange(len(prompt) - 1, len(seq))
        shorts.append(ref.shortfall(np.asarray(got["state"])[at], other,
                                    out)[0])
    shorts = np.concatenate(shorts)
    worst, median = float(shorts.max()), float(np.median(shorts))
    check(median <= TOL_LATENT_SHORTFALL and worst <= TOL_LATENT_WORST,
          f"an emitted token's logit lies {median:.4f} below the float32 "
          f"reference's largest at the median (allowed "
          f"{TOL_LATENT_SHORTFALL}) and {worst:.4f} at the worst (allowed "
          f"{TOL_LATENT_WORST})")
    tick = {p: reg.counter(counter % p).value - n for p, n in calls0.items()
            if reg.counter(counter % p).value > n}
    check(tick, "no tick counted its dense latent attention")
    say("dsv2", f"the engine's ticks compiled their dense attention by "
        f"{tick} (serving/latent_attn_calls{{kind=dense}}; the kernel where "
        f"Mosaic tiles the model's heads, latents and pages)")
    say("dsv2", f"{len(rids)} requests through latent pools alone "
        f"({cfg.num_hidden_layers} layers, two chunks a tick): shortfall "
        f"{median:.4f} at the median (allowed {TOL_LATENT_SHORTFALL}), "
        f"{worst:.4f} at the worst (allowed {TOL_LATENT_WORST}); weights "
        f"{_gb(weights)}; grouped products' tiles " + str(check_gmm_tiles(
            tiles0, cfg.hidden_size, cfg.moe_intermediate_size, "dsv2")))
    return {"worst": worst, "median": median, "weights_bytes": weights,
            "tick_paths": tick, **errs}


# ---------------------------------------------------------------------------
# phase 2d: the served gated delta rule and a hybrid model through the engine
# ---------------------------------------------------------------------------
# the three kernels of ops/gdn.py against their jax.numpy spellings on the
# same bf16 operands and float32 states, ||difference|| / ||reference|| of the
# outputs and of the states they leave (the step is float32 on the vector
# unit both ways; the chunk's products take bf16 operands both ways and
# differ by the order of their sums; the pass between projections and rule
# rounds to bf16 once where its spelling rounds after the SiLU and after the
# norm, and leaves the history bit for bit)
TOL_GDN_OPS = 1e-2
# a small hybrid model's emitted tokens against the float32 reference: the
# shortfall of an emitted token's logit below the reference's largest
TOL_GDN_SHORTFALL = 0.25
TOL_GDN_WORST = 1.0
GDN_REQUESTS = ((150, 24), (300, 20), (70, 40))


def check_gdn_ops(heads: int, dk: int, dv: int, rows: int, w: int,
                  channel: bool = False) -> dict:
    """``gdn_step_rows`` over ``rows`` decode rows and ``gdn_chunk_rows``
    over one row of ``w`` tokens, from random states, by the path observed
    here (on the chip: the kernels) against ``xla_step`` and ``xla_chunk``;
    before them ``gdn_prep_rows`` over the same two row groups (4 taps, a
    history of ``rows`` slots) against ``xla_prep``. ``channel``: the decay
    a key channel and bounded in (-5, 0), ``beta`` in (0, 1) (Ling-3.0's
    gate; the kernels ``kda_step`` and ``kda_chunk``), where Olmo-Hybrid's
    is a head's and ``beta`` in (0, 2)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import gdn

    def operands(seed, n, t):
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
        bf = lambda a: a.astype(jnp.bfloat16)
        return (bf(unit(jax.random.normal(ks[0], (n, t, heads, dk)))),
                bf(unit(jax.random.normal(ks[1], (n, t, heads, dk)))),
                bf(jax.random.normal(ks[2], (n, t, heads, dv))),
                -5 * jax.nn.sigmoid(jax.random.normal(
                    ks[3], (n, t, heads, dk)) - 2.0) if channel
                else -jax.nn.softplus(jax.random.normal(ks[3],
                                                        (n, t, heads))),
                (1 if channel else 2) * jax.nn.sigmoid(
                    jax.random.normal(ks[4], (n, t, heads))),
                jax.random.normal(ks[5], (n, heads, dk, dv)))

    rel = lambda a, b: float(jnp.linalg.norm(                # noqa: E731
        a.astype(jnp.float32) - b) / jnp.linalg.norm(b))
    path = (gdn.kda_path if channel else gdn.gdn_path)(heads, dk, dv)
    out = {"path": path}
    # the step: rows 1.. live, one dead row on the null slot
    q, k, v, g, beta, s0 = operands(1, rows, 1)
    stack = jnp.zeros((2, rows + 1) + gdn.pack_state(s0).shape[1:],
                      jnp.float32).at[1, 1:].set(gdn.pack_state(s0))
    slots = jnp.arange(1, rows + 1).at[1].set(0)
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    o, after = jax.jit(gdn.gdn_step_rows, static_argnums=6)(
        *args, stack, 1, slots)
    want_o, want_s = gdn.xla_step(*args, s0)
    live = np.asarray(slots) > 0
    out["step_o"] = rel(o[live], want_o[live])
    out["step_s"] = rel(gdn.unpack_state(after[1, 1:], heads)[live],
                        want_s[live])
    check(bool(jnp.array_equal(after[1, 2], stack[1, 2])),
          "gdn: a dead row's state moved")
    # the chunk: a carried state, the last 40 positions pads
    q, k, v, g, beta, s0 = operands(2, 1, w)
    stack = jnp.zeros((2, 3) + gdn.pack_state(s0).shape[1:],
                      jnp.float32).at[1, 2:].set(gdn.pack_state(s0))
    n_live = jnp.asarray([w - 40])
    o, after = jax.jit(gdn.gdn_chunk_rows, static_argnums=6)(
        q, k, v, g, beta, stack, 1, jnp.asarray([2]),
        jnp.zeros((1,), bool), n_live)
    want_o, want_s = gdn.xla_chunk(q, k, v, g, beta, s0, n_live)
    out["chunk_o"] = rel(o[:, :w - 40], want_o[:, :w - 40])
    out["chunk_s"] = rel(gdn.unpack_state(after[1, 2:], heads), want_s)
    check(bool(jnp.array_equal(after[1, 1], stack[1, 1])),
          "gdn: a chunk moved another slot's state")
    # the pass between projections and rule: the decode rows (one dead),
    # then a carried chunk row whose last 40 positions are pads
    c = heads * (2 * dk + dv)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    bf = lambda a: a.astype(jnp.bfloat16)                   # noqa: E731
    taps = bf(jax.random.uniform(ks[0], (4, c), minval=-0.5, maxval=0.5))
    conv = bf(jax.random.normal(ks[1], (2, 3, gdn.conv_slot_rows(rows), c)))
    conv = conv.at[:, :, 0].set(0).at[:, :, rows + 1:].set(0)
    groups = {"prep_step": (bf(jax.random.normal(ks[2], (rows, c))), slots,
                            None, None),
              "prep_chunk": (bf(jax.random.normal(ks[3], (1, w, c))),
                             jnp.asarray([2]), jnp.zeros((1,), bool), n_live)}
    out["prep_path"] = gdn.prep_path((rows, c), conv.shape, heads, dk)
    for name, (x, sl, fresh, n_tok) in groups.items():
        *got, after = jax.jit(gdn.gdn_prep_rows, static_argnums=(3, 7, 8))(
            x, taps, conv, 1, sl, fresh, n_tok, heads, dk)
        *want, left = gdn.xla_prep(x, taps, conv, 1, sl, fresh, n_tok, heads,
                                   dk)
        keep = (np.asarray(sl) > 0) if n_tok is None else slice(None)
        cut = (lambda a: a[keep]) if n_tok is None \
            else (lambda a: a[:, :w - 40])
        out[name] = max(rel(cut(a), cut(b).astype(jnp.float32))
                        for a, b in zip(got, want))
        check(bool(jnp.array_equal(after[:, :, 1:], left[:, :, 1:])),
              f"gdn: {name} leaves another history than its spelling")
    for name in ("step_o", "step_s", "chunk_o", "chunk_s", "prep_step",
                 "prep_chunk"):
        check(out[name] <= TOL_GDN_OPS, f"gdn: {name} is {out[name]:.2e} "
              f"from its jax.numpy spelling (tol {TOL_GDN_OPS})")
    return out


def serve_against_reference(tag: str, net, sizes, requests, forward,
                            counters: dict, want_path: str, what: str) -> dict:
    """What the two phases of a model with a state a slot share: ``net`` (a
    ``LazyGuard`` model, drawn on the device in bf16) through an engine of
    ``sizes`` (slots, page, pages a slot, chunk), prompts of several chunks;
    what it emitted is the float32 reference's (``forward(layers, other,
    tokens) -> {"state"}`` and the reference module ``forward.ref`` for the
    shortfall), and its ticks counted every counter of ``counters`` (``{kind:
    name with %s for the path}``) by ``want_path`` alone."""
    import jax

    from paddle_tpu.profiler import registry
    from paddle_tpu.serving import ServingConfig, ServingEngine

    cfg, reg = net.config, registry()
    calls0 = {(kind, p): reg.counter(c % p).value
              for kind, c in counters.items() for p in ("pallas", "xla")}
    net.eval()
    net.bfloat16()
    num_slots, page_size, pages_per_slot, chunk = sizes
    eng = ServingEngine(net, ServingConfig(
        num_slots=num_slots, page_size=page_size,
        pages_per_slot=pages_per_slot, prefill_chunk=chunk,
        prefix_cache=False))
    layers, other = eng.served_weights()
    on_default_platform((layers, other, eng.pool.pools),
                        f"{tag} serving state")
    weights = reg.gauge("serving/weights_bytes").value
    check(weights == 2 * cfg.num_params(),
          f"serving/weights_bytes {weights:.0f} is not one bf16 copy of "
          f"{cfg.num_params()} parameters")
    rng = np.random.RandomState(13)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n, _ in requests]
    rids = [eng.submit(p, new) for p, (_, new) in zip(prompts, requests)]
    results = eng.run()
    jax.block_until_ready(eng.pool.pools)
    check(eng.pool.check_consistency() == [], "the pools' books disagree")
    shorts = []
    for rid, prompt in zip(rids, prompts):
        out = results[rid]
        seq = np.concatenate([prompt, out[:-1]])
        got = forward(layers, other, seq)
        at = np.arange(len(prompt) - 1, len(seq))
        shorts.append(forward.ref.shortfall(
            np.asarray(got["state"])[at], other, out)[0])
    shorts = np.concatenate(shorts)
    worst, median = float(shorts.max()), float(np.median(shorts))
    check(median <= TOL_GDN_SHORTFALL and worst <= TOL_GDN_WORST,
          f"an emitted token's logit lies {median:.4f} below the float32 "
          f"reference's largest at the median (allowed {TOL_GDN_SHORTFALL}) "
          f"and {worst:.4f} at the worst (allowed {TOL_GDN_WORST})")
    tick = {kind: sorted(p for p in ("pallas", "xla") if reg.counter(
        c % p).value > calls0[(kind, p)]) for kind, c in counters.items()}
    check(tick == dict.fromkeys(counters, [want_path]),
          f"the engine's ticks counted {tick}, not {want_path} alone ("
          + ", ".join(c % "" for c in counters.values()) + ")")
    say(tag, f"{len(rids)} requests through {what} "
        f"({cfg.num_hidden_layers} layers, chunks of {chunk}): shortfall "
        f"{median:.4f} at the median (allowed {TOL_GDN_SHORTFALL}), "
        f"{worst:.4f} at the worst (allowed {TOL_GDN_WORST}); the ticks' "
        f"paths {tick}; weights {_gb(weights)}")
    return {"worst": worst, "median": median, "weights_bytes": weights,
            "tick_paths": tick}


#: the served delta rule's three counters, the path left open
GDN_COUNTERS = {kind: "gdn/%s_calls{path=%%s}" % kind
                for kind in ("step", "chunk", "prep")}


def phase_olmoh(cfg, num_slots: int, page_size: int, pages_per_slot: int,
                chunk: int, ops_shape, want_path: str,
                requests=GDN_REQUESTS) -> dict:
    """Olmo-Hybrid's pass (models/olmo_hybrid.py): the three kernels of the
    served gated delta rule (the pass between projections and rule, the
    step, the chunk) against their ``jax.numpy`` spellings at ``ops_shape``
    (heads, dk, dv, decode rows, chunk tokens), which must go by
    ``want_path`` (on the chip the kernels'), then a small model of both
    kinds of layer through the engine (a ``LazyGuard`` model drawn on the
    device, K/V pages beside a state a slot, prompts of several chunks):
    what it emitted is the float32 reference's
    (models/olmo_hybrid_reference.py), and its ticks counted their delta
    rule by ``want_path``."""
    import dataclasses as dc

    import paddle_tpu as paddle
    from paddle_tpu.models import olmo_hybrid_reference as ref
    from paddle_tpu.models.olmo_hybrid import OlmoHybrid

    errs = check_gdn_ops(*ops_shape)
    path, prep = errs.pop("path"), errs.pop("prep_path")
    say("olmoh", f"{ops_shape[0]} heads of {ops_shape[1]} x {ops_shape[2]}, "
        f"{ops_shape[3]} decode rows and a chunk of {ops_shape[4]}: "
        + ", ".join(f"{k} {v:.2e}" for k, v in errs.items())
        + f" from the jax.numpy spellings (allowed {TOL_GDN_OPS}); path "
        f"here: {path}")
    check(path == prep == want_path, f"the delta rule of {ops_shape[:3]} "
          f"went by {path} and what lies before it by {prep}, not "
          f"{want_path}")
    paddle.seed(0)
    with paddle.LazyGuard():
        net = OlmoHybrid(cfg)

    def forward(layers, other, seq):
        return ref.forward(((kind, layers[f"layer{i}"])
                            for i, kind in enumerate(cfg.layer_types)),
                           other, seq, dc.asdict(cfg))

    forward.ref = ref
    return {**serve_against_reference(
        "olmoh", net, (num_slots, page_size, pages_per_slot, chunk),
        requests, forward, GDN_COUNTERS, want_path,
        "K/V pages and a state a slot"), **errs}


LING_REQUESTS = ((300, 24), (520, 20), (140, 40))


def phase_ling(cfg, num_slots: int, page_size: int, pages_per_slot: int,
               chunk: int, ops_shape, want_path: str,
               requests=LING_REQUESTS) -> dict:
    """Ling-3.0's pass (models/ling3.py): the served delta rule **with a
    decay a key channel** (the kernels ``kda_step`` and ``kda_chunk``, and
    the pass before them at these widths) against the ``jax.numpy``
    spellings at ``ops_shape`` (heads, dk, dv, decode rows, chunk tokens),
    which must go by ``want_path`` (on the chip the kernels'), then a small
    model of both kinds of layer and a routed FFN through the engine (a
    state a slot beside latent pages in one pool): what it emitted is the
    float32 reference's (models/ling3_reference.py), and its ticks counted
    their state step, chunk and pass and their latent attention by
    ``want_path``."""
    import dataclasses as dc

    import paddle_tpu as paddle
    from paddle_tpu.models import ling3_reference as ref
    from paddle_tpu.models.ling3 import Ling3, Ling3Config
    from paddle_tpu.ops.grouped_matmul import tile_for

    errs = check_gdn_ops(*ops_shape, channel=True)
    path, prep = errs.pop("path"), errs.pop("prep_path")
    say("ling", f"{ops_shape[0]} heads of {ops_shape[1]} x {ops_shape[2]}, a "
        f"decay a channel, {ops_shape[3]} decode rows and a chunk of "
        f"{ops_shape[4]}: " + ", ".join(f"{k} {v:.2e}"
                                        for k, v in errs.items())
        + f" from the jax.numpy spellings (allowed {TOL_GDN_OPS}); path "
        f"here: {path}")
    check(path == prep == want_path, f"the per-channel rule of "
          f"{ops_shape[:3]} went by {path} and what lies before it by "
          f"{prep}, not {want_path}")
    paddle.seed(0)
    with paddle.LazyGuard():
        net = Ling3(cfg)

    def forward(layers, other, seq):
        return ref.forward(((kind, cfg.is_moe(i), layers[f"layer{i}"])
                            for i, kind in enumerate(cfg.layer_kinds)),
                           other, seq, dc.asdict(cfg), held=cfg.held)

    forward.ref = ref
    counters = dict(GDN_COUNTERS,
                    latent="serving/latent_attn_calls{path=%s,kind=dense}")
    tiles0 = gmm_tiles()
    out = serve_against_reference(
        "ling", net, (num_slots, page_size, pages_per_slot, chunk),
        requests, forward, counters, want_path,
        "a state a slot beside latent pages")
    # the routed experts' products: one tile each, the whole contraction a
    # step, at this model's widths and at the published ones (a function of
    # the sides: nothing is run for those)
    tiles = check_gmm_tiles(tiles0, cfg.hidden_size,
                            cfg.moe_intermediate_size, "ling")
    wide = Ling3Config()
    h, f = wide.hidden_size, wide.moe_intermediate_size
    gate_up, down = tile_for(1024, h, f), tile_for(1024, f, h)
    check(gate_up == (128, h, f) and down == (128, f, h),
          f"an expert of [{h}, {f}] is not one grid step a visit: "
          f"{gate_up}, {down}")
    say("ling", f"the ticks' grouped products counted the tiles {tiles} "
        f"(moe/grouped_matmul_tiles); at [{h}, {f}] the rule gives "
        f"{gate_up} and {down}")
    return {**out, **errs, "gmm_tiles": tiles}


#: the served state-space rule's three counters and the attention's
SSD_COUNTERS = dict(
    {kind: "ssd/%s_calls{path=%%s}" % kind
     for kind in ("step", "chunk", "prep")},
    attn="serving/attn_calls{path=%s}")


def check_ssd_ops(heads: int, p: int, n: int, groups: int, rows: int,
                  w: int) -> dict:
    """``ssd_step_rows`` over ``rows`` decode rows and ``ssd_chunk_rows``
    over one row of ``w`` tokens, from random states, by the path observed
    here (on the chip: the kernels ``ssd_step`` and ``ssd_chunk``) against
    ``xla_step`` and ``xla_chunk``; before them ``ssd_prep_rows`` over the
    same two row groups (4 taps and a bias, a history of ``rows`` slots)
    against ``xla_prep``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import gdn, ssd

    def operands(seed, lead):
        ks = jax.random.split(jax.random.PRNGKey(seed), 7)
        bf = lambda a: a.astype(jnp.bfloat16)               # noqa: E731
        return (bf(jax.random.normal(ks[0], lead + (heads, p))),
                bf(jax.random.normal(ks[1], lead + (groups, n))),
                bf(jax.random.normal(ks[2], lead + (groups, n))),
                jax.nn.softplus(jax.random.normal(ks[3], lead + (heads,))
                                - 3.0),
                -jax.random.uniform(ks[4], (heads,), minval=1.0,
                                    maxval=16.0),
                jnp.ones((heads,)),
                jax.random.normal(ks[5], lead[:1] + (heads, n, p)))

    rel = lambda a, b: float(jnp.linalg.norm(                # noqa: E731
        a.astype(jnp.float32) - b) / jnp.linalg.norm(b))
    path = ssd.ssd_path(heads, n, p)
    out = {"path": path}
    # the step: rows 1.. live, one dead row on the null slot
    x, B, C, dt, A, D, s0 = operands(1, (rows,))
    stack = jnp.zeros((2, rows + 1, heads, n, p),
                      jnp.float32).at[1, 1:].set(s0)
    slots = jnp.arange(1, rows + 1).at[1].set(0)
    y, after = jax.jit(ssd.ssd_step_rows, static_argnums=7)(
        x, B, C, dt, A, D, stack, 1, slots)
    want_y, want_s = ssd.xla_step(x, B, C, dt, A, D, s0)
    live = np.asarray(slots) > 0
    out["step_y"] = rel(y[live], want_y[live])
    out["step_s"] = rel(after[1, 1:][live], want_s[live])
    check(bool(jnp.array_equal(after[1, 2], stack[1, 2])),
          "ssd: a dead row's state moved")
    # the chunk: a carried state, the last 40 positions pads
    x, B, C, dt, A, D, s0 = operands(2, (1, w))
    stack = jnp.zeros((2, 3, heads, n, p), jnp.float32).at[1, 2:].set(s0)
    n_live = jnp.asarray([w - 40])
    y, after = jax.jit(ssd.ssd_chunk_rows, static_argnums=7)(
        x, B, C, dt, A, D, stack, 1, jnp.asarray([2]),
        jnp.zeros((1,), bool), n_live)
    f32 = lambda a: a.astype(jnp.float32)                   # noqa: E731
    want_y, want_s = ssd.ssd_recurrent(
        f32(x)[:, :w - 40], f32(B)[:, :w - 40], f32(C)[:, :w - 40],
        dt[:, :w - 40], A, D, s0)
    out["chunk_y"] = rel(y[:, :w - 40], want_y)
    out["chunk_s"] = rel(after[1, 2:], want_s)
    check(bool(jnp.array_equal(after[1, 1], stack[1, 1])),
          "ssd: a chunk moved another slot's state")
    # the pass between projection and rule: the decode rows (one dead), then
    # a carried chunk row whose last 40 positions are pads
    c = heads * p + 2 * groups * n
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    bf = lambda a: a.astype(jnp.bfloat16)                   # noqa: E731
    taps = bf(jax.random.uniform(ks[0], (4, c), minval=-0.5, maxval=0.5))
    bias = bf(jax.random.uniform(ks[4], (c,), minval=-0.5, maxval=0.5))
    conv = bf(jax.random.normal(ks[1], (2, 3, gdn.conv_slot_rows(rows), c)))
    conv = conv.at[:, :, 0].set(0).at[:, :, rows + 1:].set(0)
    row_groups = {
        "prep_step": (bf(jax.random.normal(ks[2], (rows, c))), slots, None,
                      None),
        "prep_chunk": (bf(jax.random.normal(ks[3], (1, w, c))),
                       jnp.asarray([2]), jnp.zeros((1,), bool), n_live)}
    out["prep_path"] = ssd.prep_path((rows, c), conv.shape)
    for name, (xs, sl, fresh, n_tok) in row_groups.items():
        got, after = jax.jit(ssd.ssd_prep_rows, static_argnums=4)(
            xs, taps, bias, conv, 1, sl, fresh, n_tok)
        want, left = ssd.xla_prep(xs, taps, bias, conv, 1, sl, fresh, n_tok)
        cut = (lambda a: a[np.asarray(sl) > 0]) if n_tok is None \
            else (lambda a: a[:, :w - 40])
        out[name] = rel(cut(got), cut(want).astype(jnp.float32))
        check(bool(jnp.array_equal(after[:, :, 1:], left[:, :, 1:])),
              f"ssd: {name} leaves another history than its spelling")
    for name in ("step_y", "step_s", "chunk_y", "chunk_s", "prep_step",
                 "prep_chunk"):
        check(out[name] <= TOL_GDN_OPS, f"ssd: {name} is {out[name]:.2e} "
              f"from its reference (tol {TOL_GDN_OPS})")
    return out


def check_grouped_attention(rows: int, t: int, heads: int, kvh: int, d: int,
                            ps: int, nps: int, live: int,
                            window: int = None) -> float:
    """``grouped_paged_attention`` by the platform's path (on the chip the
    kernel ``grouped_paged_attn``; under ``window`` its sibling
    ``grouped_window_attn``) against its XLA spelling: ``rows`` rows of
    ``t`` queries over ``live`` positions each, bf16 pages of ``kvh`` heads
    under ``heads`` query heads, written by ``grouped_kv_scatter``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_attention as pa

    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    pages = -(-live // ps)
    table = (1 + jnp.arange(rows * pages, dtype=jnp.int32)).reshape(
        rows, pages)
    table = jnp.pad(table, ((0, 0), (0, nps - pages)))
    pool = jnp.zeros((1, rows * pages + 1, 2 * kvh, ps, d), jnp.bfloat16)
    pos = jnp.tile(jnp.arange(live, dtype=jnp.int32), rows)
    page = table[jnp.repeat(jnp.arange(rows), live), pos // ps]
    kv = jax.random.normal(ks[0], (2, rows * live, kvh, d)).astype(
        jnp.bfloat16)
    pool = jax.jit(pa.grouped_kv_scatter, static_argnums=5)(
        pool, page, pos % ps, kv[0], kv[1], 0, table.reshape(-1))
    q = jax.random.normal(ks[1], (rows, t, heads, d)).astype(jnp.bfloat16)
    pos0 = jnp.full((rows,), live - t, jnp.int32)
    tl = jnp.full((rows,), t, jnp.int32)
    args = (q, pool, table, pos0, tl, 0)
    got = jax.jit(pa.grouped_paged_attention, static_argnums=(5, 6, 7))(
        *args, None, window)
    want = jax.jit(pa.grouped_paged_attention, static_argnums=(5, 6, 7))(
        *args, "xla", window)
    return _nerr(got, want)


def ragged_rows(rng, rows: int, t: int, ps: int, nps: int, window=None):
    """One draw of ragged rows for ``check_grouped_attention_ragged``: how
    many positions each row holds after its queries (``last``; 0: a row of
    no tokens), its ``pos0`` and ``true_len``, and a table of shuffled page
    ids that names a row's live pages alone (null entries past them and,
    under ``window``, for every page wholly behind the row's oldest visible
    key). Every edge a walk has is some row's last position: 1, ``ps - 1``,
    ``ps``, 255 to 257, 511 to 513 and the slot's capacity; about one row
    in six holds nothing, two of them neighbours; a row of ``t > 1`` queries
    in three has a ``true_len`` short of ``t``."""
    cap = ps * nps
    last = rng.integers(1, cap + 1, size=rows)
    last[rng.random(rows) < 0.15] = 0
    pair = rng.integers(rows - 1)
    last[[pair, pair + 1]] = 0
    edges = [e for e in (1, ps - 1, ps, 255, 256, 257, 511, 512, 513, cap)
             if 0 < e <= cap]
    free = [i for i in rng.permutation(rows) if i not in (pair, pair + 1)]
    edges = rng.permutation(edges)[:len(free)]
    last[free[:len(edges)]] = edges
    tl = np.minimum(last, t)
    short = (rng.random(rows) < 1 / 3) & (tl > 1)
    tl[short] = rng.integers(1, tl[short])
    pos0 = last - tl
    ids = 1 + rng.permutation(rows * nps).reshape(rows, nps)
    col = np.arange(nps)[None, :]
    held = col < -(-last[:, None] // ps)
    if window is not None:
        held &= col >= np.maximum(pos0[:, None] - (window - 1), 0) // ps
    return last, pos0, tl, np.where(held, ids, 0)


def check_grouped_attention_ragged(rows: int, t: int, heads: int, kvh: int,
                                   d: int, ps: int, nps: int, window=None,
                                   draws: int = 20, seed: int = 61) -> float:
    """``grouped_paged_attention`` by the platform's path against its XLA
    spelling on rows of mixed depth in one call (``ragged_rows``, ``draws``
    draws from one seeded generator over one pool of bf16 pages, every
    position of every page written). Compared are the live queries, a row
    at its own scale; a draw over ``TOL_RAGGED`` fails and names its worst
    row. Returned: the worst draw's error."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    pool = jax.random.normal(ks[0], (1, rows * nps + 1, 2 * kvh, ps, d),
                             jnp.bfloat16)
    pool = pool.at[:, 0].set(0)
    q = jax.random.normal(ks[1], (rows, t, heads, d)).astype(jnp.bfloat16)
    # traced anew in every check (a function of its own)
    attend = jax.jit(lambda *a: pa.grouped_paged_attention(*a),
                     static_argnums=(5, 6, 7))
    worst = 0.0
    for draw in range(draws):
        last, pos0, tl, table = ragged_rows(rng, rows, t, ps, nps, window)
        args = (q, pool, jnp.asarray(table, jnp.int32),
                jnp.asarray(pos0, jnp.int32), jnp.asarray(tl, jnp.int32), 0)
        got = np.asarray(attend(*args, None, window), np.float32)
        want = np.asarray(attend(*args, "xla", window), np.float32)
        live = np.arange(t)[None, :] < tl[:, None]              # [R, T]
        err = np.abs(got - want).max(axis=(2, 3))
        err = np.where(live, err, 0).max(axis=1) / np.maximum(
            np.abs(want).max(axis=(1, 2, 3)), 1e-30)
        r = int(np.argmax(err))
        check(np.isfinite(got[live]).all() and err[r] <= TOL_RAGGED,
              f"grouped attention, ragged rows {(rows, t, heads, kvh)} "
              f"window {window}, draw {draw} of seed {seed}: row {r} (pos0 "
              f"{pos0[r]}, true_len {tl[r]}, {last[r]} positions) is "
              f"{err[r]:.2e} from the XLA spelling (tol {TOL_RAGGED}); rows "
              f"of no tokens: {np.flatnonzero(last == 0).tolist()}")
        check(not got[tl == 0].any(), f"grouped attention, draw {draw}: a "
              "row of no tokens did not read zeros")
        worst = max(worst, float(err[r]))
    return worst


def say_ragged(phase: str, shapes) -> dict:
    """``check_grouped_attention_ragged`` at each of ``shapes``, said."""
    errs = {}
    for shape in shapes:
        window = shape[7] if len(shape) > 7 else None
        err = check_grouped_attention_ragged(*shape)
        errs["ragged_%dx%dx%d" % shape[:3]] = err
        say(phase, f"grouped attention on ragged rows, {shape[0]} rows of "
            f"up to {shape[1]} queries, {shape[2]} heads over {shape[3]}, "
            f"pages of {shape[5]}, slots of {shape[5] * shape[6]}, window "
            f"{window}: twenty draws at most {err:.2e} from the XLA spelling "
            f"(allowed {TOL_RAGGED})")
    return errs


def grouped_operands() -> dict:
    """``serving/grouped_attn_operand{queries=,rows=}``: the grouped
    kernel's calls by how many queries a key/value head's operand held and
    how many rows it was given; counted where the wrapper is traced."""
    from paddle_tpu.profiler import registry

    head = "serving/grouped_attn_operand{"
    return {tuple(int(x.split("=")[1]) for x in name[len(head):-1].split(",")):
            m["value"] for name, m in registry().snapshot().items()
            if name.startswith(head)}


def check_decode_operand(before: dict, groups, want_path: str,
                         what: str) -> None:
    """The ticks traced since ``before`` (``grouped_operands()`` then) gave
    a decode row's ``G`` queries, for each ``G`` of ``groups``, one tile of
    16 rows (bf16); off the chip nothing takes the kernel and nothing is
    counted."""
    new = {k: v - before.get(k, 0) for k, v in grouped_operands().items()
           if v != before.get(k, 0)}
    if want_path != "pallas":
        check(not new, f"{what}: operands counted off the kernel: {new}")
        return
    for g in groups:
        check({rows for (queries, rows) in new if queries == g} == {16},
              f"{what}: a decode row's {g} queries a key/value head were not "
              f"one tile of 16 rows: counted {new} "
              "(serving/grouped_attn_operand)")
    say(what, f"the ticks' grouped attention counted the operands {new} "
        "(queries, rows a key/value head)")


FALCON_REQUESTS = ((300, 24), (520, 20), (140, 40))


def phase_falcon(cfg, num_slots: int, page_size: int, pages_per_slot: int,
                 chunk: int, ops_shape, attn_shapes, want_path: str,
                 requests=FALCON_REQUESTS, ragged=()) -> dict:
    """Falcon-H1's pass (models/falcon_h1.py): the served state-space rule's
    kernels (``ssd_step``, ``ssd_chunk`` and the pass before them) against
    their references at ``ops_shape`` (heads, P, N, groups, decode rows,
    chunk tokens), which must go by ``want_path`` (on the chip the
    kernels'); grouped-query attention over pages without padded heads at
    ``attn_shapes`` (the cell's decode rows and a chunk row's piece) against
    its XLA spelling, and on ragged rows at ``ragged``
    (``check_grouped_attention_ragged``'s arguments); then a small model
    through the engine (a state a slot **and** grouped K/V pages in every
    layer): what it emitted is the float32 reference's
    (models/falcon_h1_reference.py; shortfalls in units of a position's own
    spread of logits), its ticks counted their step, chunk, pass and
    attention by ``want_path``, and a decode row's queries were one tile of
    the kernel's operand (``check_decode_operand``)."""
    import dataclasses as dc
    import types

    import paddle_tpu as paddle
    from paddle_tpu.models import falcon_h1_reference as ref
    from paddle_tpu.models.falcon_h1 import FalconH1

    errs = check_ssd_ops(*ops_shape)
    path, prep = errs.pop("path"), errs.pop("prep_path")
    say("falcon", f"{ops_shape[0]} heads of {ops_shape[1]} x {ops_shape[2]} "
        f"in {ops_shape[3]} groups, {ops_shape[4]} decode rows and a chunk "
        f"of {ops_shape[5]}: " + ", ".join(f"{k} {v:.2e}"
                                           for k, v in errs.items())
        + f" from their references (allowed {TOL_GDN_OPS}); path here: "
        f"{path}")
    check(path == prep == want_path, f"the state-space rule of "
          f"{ops_shape[:4]} went by {path} and what lies before it by "
          f"{prep}, not {want_path}")
    for shape in attn_shapes:
        err = check_grouped_attention(*shape)
        errs["attn_%dx%d" % shape[:2]] = err
        say("falcon", f"grouped attention, {shape[0]} rows of {shape[1]} "
            f"queries, {shape[2]} heads over {shape[3]}, {shape[7]} live "
            f"positions in pages of {shape[5]}: {err:.2e} from the XLA "
            f"spelling (allowed {TOL_RAGGED})")
        check(err <= TOL_RAGGED, f"grouped attention {shape} is {err:.2e} "
              f"from its XLA spelling (tol {TOL_RAGGED})")
    errs.update(say_ragged("falcon", ragged))
    operands = grouped_operands()
    paddle.seed(0)
    with paddle.LazyGuard():
        net = FalconH1(cfg)
    config = dc.asdict(cfg)

    def forward(layers, other, seq):
        return ref.forward((layers[f"layer{i}"]
                            for i in range(cfg.num_hidden_layers)),
                           other, seq, config)

    def shortfall(state, other, targets):
        short, mine, sigma = ref.shortfall(state, other, config, targets)
        return short / sigma, mine

    forward.ref = types.SimpleNamespace(shortfall=shortfall)
    out = serve_against_reference(
        "falcon", net, (num_slots, page_size, pages_per_slot, chunk),
        requests, forward, SSD_COUNTERS, want_path,
        "a state a slot and grouped K/V pages in every layer")
    check_decode_operand(
        operands, [cfg.num_attention_heads // cfg.num_key_value_heads],
        want_path, "falcon")
    return {**out, **errs}


LAGUNA_REQUESTS = ((300, 24), (520, 20), (140, 40))
#: a tick of Laguna's counts its two kinds of attention in one counter
LAGUNA_COUNTERS = {"attn": "serving/attn_calls{path=%s}"}


def phase_laguna(cfg, num_slots: int, page_size: int, pages_per_slot: int,
                 chunk: int, attn_shapes, want_path: str,
                 requests=LAGUNA_REQUESTS, ragged=()) -> dict:
    """Laguna's pass (models/laguna.py): grouped-query attention under a
    sliding window at ``attn_shapes`` (``check_grouped_attention``'s
    arguments, the window last: the cell's decode rows and a chunk row's
    piece at six and nine query heads a key/value head) against its XLA
    spelling, and on ragged rows at ``ragged``
    (``check_grouped_attention_ragged``'s arguments: full and windowed);
    then a small model through the engine (full and windowed
    layers of unlike query heads over one set of key/value heads, a gate a
    head, held experts; the windowed layers' pages freed behind the
    window): what it emitted is the float32 reference's
    (models/laguna_reference.py; shortfalls in units of a position's own
    spread of logits), its ticks counted their attention by ``want_path``,
    and a decode row's queries were one tile of the kernel's operand in both
    kinds of layer (``check_decode_operand``)."""
    import dataclasses as dc
    import types

    import paddle_tpu as paddle
    from paddle_tpu.models import laguna_reference as ref
    from paddle_tpu.models.laguna import Laguna
    from paddle_tpu.profiler import registry

    errs = {}
    for shape in attn_shapes:
        err = check_grouped_attention(*shape)
        errs["attn_%dx%dx%d" % (shape[0], shape[1], shape[2])] = err
        say("laguna", f"windowed grouped attention, {shape[0]} rows of "
            f"{shape[1]} queries, {shape[2]} heads over {shape[3]}, "
            f"{shape[7]} live positions in pages of {shape[5]}, a window of "
            f"{shape[8]}: {err:.2e} from the XLA spelling (allowed "
            f"{TOL_RAGGED})")
        check(err <= TOL_RAGGED, f"windowed grouped attention {shape} is "
              f"{err:.2e} from its XLA spelling (tol {TOL_RAGGED})")
    errs.update(say_ragged("laguna", ragged))
    operands = grouped_operands()
    paddle.seed(0)
    with paddle.LazyGuard():
        net = Laguna(cfg)
    config = dc.asdict(cfg)
    freed0 = registry().counter("serving/window_pages_freed").value

    def forward(layers, other, seq):
        return ref.forward((layers[f"layer{i}"]
                            for i in range(cfg.num_hidden_layers)),
                           other, seq, config, held=cfg.held)

    def shortfall(state, other, targets):
        short, top, sigma = ref.shortfall(state, other, targets)
        return short / sigma, top

    forward.ref = types.SimpleNamespace(shortfall=shortfall)
    out = serve_against_reference(
        "laguna", net, (num_slots, page_size, pages_per_slot, chunk),
        requests, forward, LAGUNA_COUNTERS, want_path,
        "full and windowed grouped K/V pages and held experts")
    freed = registry().counter("serving/window_pages_freed").value - freed0
    check(freed > 0, "no page of the windowed layers was freed behind the "
          "window")
    check_decode_operand(
        operands, sorted({n // cfg.num_key_value_heads
                          for n in cfg.num_attention_heads_per_layer}),
        want_path, "laguna")
    return {**out, **errs, "window_pages_freed": freed}


# ---------------------------------------------------------------------------
# phases 3 and 4: the hybrid trainer
# ---------------------------------------------------------------------------
def flash_in_program(tr, tokens, what: str) -> None:
    """The step program handed to XLA (a diagnostic re-lowering) holds the
    Pallas flash kernel, not the jnp path of nn/functional/attention.py.
    An interpreted kernel is plain HLO, so there only the lowering runs."""
    from paddle_tpu.ops import flash_attention as fa

    text = tr.aot_lower(tokens).as_text()
    check(fa._interpret() or "tpu_custom_call" in text,
          f"{what}: no Pallas flash kernel in the step program")


def train_steps(cfg, mesh_axes: dict, devices, micro: int, n_micro: int,
                steps: int) -> dict:
    """``steps`` steps of ``HybridPipelineTrainer`` with bench.py's
    headline knobs (bf16 params and moments, recompute, free_eager) on a
    fixed batch, each ended by block_until_ready. Returns losses, times,
    the trainer's state arrays and whether the step program holds the
    Pallas flash kernel."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh
    from paddle_tpu.models import GPT
    from paddle_tpu.profiler import recompile

    paddle.seed(0)
    model = GPT(cfg)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    s = DistributedStrategy()
    s.amp = True
    s.recompute = True
    axes = {"dp": 1, "pp": 1, "tp": 1, "sp": 1, **mesh_axes}
    mesh = create_mesh(axes, list(devices))
    tr = HybridPipelineTrainer(model, opt, s, mesh, n_micro=n_micro,
                               param_dtype="bfloat16",
                               moment_dtype="bfloat16", free_eager=True)
    built_peak = peak_bytes()    # f32 eager init + the trainer's copies
    seq = cfg.max_seq_len
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (micro * n_micro, seq)).astype(np.int32)
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(tr.step(tokens))
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    what = f"train {mesh_axes or '1 chip'}"
    check(np.isfinite(losses).all(), f"{what}: loss not finite {losses}")
    check(losses[-1] < losses[0], f"{what}: loss not descending {losses}")
    check(recompile.trace_counts().get(tr._prof_site) == 1,
          f"{what}: the step retraced: "
          f"{recompile.trace_counts().get(tr._prof_site)} traces")
    state = (tr.block_vals, tr.other_vals, tr.block_opt, tr.other_opt)
    on_default_platform(state, what)
    flash_in_program(tr, tokens, what)     # after the retrace check
    return {"losses": losses, "times": times, "state": state,
            "tokens_per_step": int(tokens.size), "built_peak": built_peak}


def phase_train(cfg, micro: int, n_micro: int, steps: int) -> dict:
    import jax

    r = train_steps(cfg, {}, jax.devices()[:1], micro, n_micro, steps)
    steady = min(r["times"][1:]) if len(r["times"]) > 1 else r["times"][0]
    say("train", f"{steps} steps x {r['tokens_per_step']} tokens, losses "
        + " ".join(f"{x:.4f}" for x in r["losses"]))
    say("train", f"first step (compile) {r['times'][0]:.1f} s, later steps "
        + " ".join(f"{x:.2f}" for x in r["times"][1:])
        + f" s (best {steady:.2f} s), Pallas flash in the program, one "
        f"trace; peak so far {_gb(r['built_peak'])} once the trainer was "
        f"built, {_gb(peak_bytes())} after the steps")
    return {"loss0": r["losses"][0]}


def _distinct_devices(tree) -> int:
    import jax

    return len({s.device for leaf in jax.tree_util.tree_leaves(tree)
                for s in leaf.addressable_shards})


def phase_multichip(cfg, micro: int, n_micro: int, loss0: float,
                    zero_cfg, zero_batch: int, head) -> None:
    """Four chips: the trainer on dp2 x tp2 (pp = 1) and pp2 x tp2 (whose
    stages must have shared the loss head: ``head/pp_share_traces``), the
    head alone (``head``: ``check_head``'s shape and dtype) as the
    pp2 x tp2 arm runs it, then
    compile_train_step on dp=4 replicated / ZeRO-1 f32 ring / ZeRO-2 int8
    ring."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.mesh import create_mesh
    from paddle_tpu.distributed.strategy_compiler import compile_train_step
    from paddle_tpu.models import GPT
    from paddle_tpu.profiler import metrics

    def head_shares():
        reg = metrics.registry()
        return {n: reg.counter("head/pp_share_traces{stages=%d}" % n).value
                for n in (1, 2)}

    devs = jax.devices()[:4]
    for axes in ({"dp": 2, "tp": 2}, {"pp": 2, "tp": 2}):
        before = head_shares()
        r = train_steps(cfg, axes, devs, micro, n_micro, steps=2)
        shared = {n: v - before[n] for n, v in head_shares().items()}
        # which head the step compiled (pipeline.py "Loss egress"): no
        # region at pp = 1, and on the CPU under amp the trainer keeps the
        # head outside it; on the chip the stages share it, or each runs
        # all of it where the micro-batches do not divide
        stages = 0 if "pp" not in axes or devs[0].platform == "cpu" \
            else 2 if n_micro % 2 == 0 else 1
        check(all((v > 0) == (n == stages) for n, v in shared.items()),
              f"train {axes}: the step counted head/pp_share_traces "
              f"{shared}, expected stages={stages or 'none'}")
        rel = abs(r["losses"][0] - loss0) / abs(loss0)
        check(rel < 0.02, f"train {axes}: step-0 loss {r['losses'][0]} vs "
              f"one chip {loss0} ({rel:.3%})")
        params, opt_state = r["state"][:2], r["state"][2:]
        n_p, n_o = _distinct_devices(params), _distinct_devices(opt_state)
        check(n_p == 4 and n_o == 4, f"train {axes}: state on {n_p}/{n_o} "
              "devices, expected 4")
        say("multichip", f"{axes}: losses "
            + " ".join(f"{x:.4f}" for x in r["losses"])
            + f" (step 0 within {rel:.2%} of one chip), head shared by "
            f"{stages or 'no'} stages, first step "
            f"{r['times'][0]:.1f} s, second {r['times'][1]:.2f} s, params "
            f"and optimizer shards on {n_p} devices")
        del r, params, opt_state
        release_device_memory()

    errs = check_head(*head, mesh=create_mesh({"pp": 2, "tp": 2}, devs))
    say("multichip", f"head {head[:5]} inside a region manual over pp, "
        "vocabulary over tp, " + _head_errs(errs))
    release_device_memory()

    tokens = np.random.RandomState(0).randint(
        0, zero_cfg.vocab_size,
        (zero_batch, zero_cfg.max_seq_len)).astype(np.int32)
    ledgers = {}
    for name, stage, comm in (("replicated", 0, "f32"),
                              ("zero1_f32_ring", 1, "f32"),
                              ("zero2_int8_ring", 2, "int8")):
        paddle.seed(3)
        net = GPT(zero_cfg)
        opt = paddle.optimizer.AdamW(2e-3, parameters=net.parameters())
        s = DistributedStrategy()
        if stage:
            s.sharding = True
            s.sharding_configs = {"sharding_stage": stage}
        tr = compile_train_step(net, opt, s, create_mesh({"dp": 4}, devs),
                                dp_grad_comm=comm, dp_grad_block=512)
        losses = [float(jax.block_until_ready(tr.step(tokens)))
                  for _ in range(2)]
        check(np.isfinite(losses).all() and losses[1] < losses[0],
              f"{name}: losses {losses}")
        on_default_platform((tr.params, tr.opt_states), name)
        check(_distinct_devices(tr.params) == 4,
              f"{name}: params not on 4 devices")
        flash_in_program(tr, tokens, f"dp=4 {name}")
        ledgers[name] = tr.memory_ledger()["opt_state"]
        say("multichip", f"dp=4 {name}: losses {losses[0]:.4f} "
            f"{losses[1]:.4f}, Pallas flash in the program, opt_state "
            f"{ledgers[name]} bytes per rank")
    for name in ("zero1_f32_ring", "zero2_int8_ring"):
        ratio = ledgers[name] / ledgers["replicated"]
        check(0.2 < ratio < 0.3, f"{name}: opt_state {ratio:.3f} of "
              "replicated, expected about 1/4")


# ---------------------------------------------------------------------------
# phase 1c: the small Solar-Open2 through the trainer
# ---------------------------------------------------------------------------
# the trainer's bf16 loss against models/solar_open2_reference.loss in f32
# on the same weights: a mean over a few hundred positions of a model whose
# products take bf16 operands (seen on the v5e, PR 31: 2e-4)
TOL_SOLAR_LOSS = 2e-3
# the scan alone, bf16 operands, output and the five gradients against
# autodiff of the token-by-token recurrence in float32, ||difference|| /
# ||reference|| (seen on the v5e, PR 31: 4.6e-3 to 4.9e-3; the decay's
# gradient, a difference of large terms, 2.0e-2)
TOL_SOLAR_SCAN = {"o": 2e-2, "dq": 2e-2, "dk": 2e-2, "dv": 2e-2, "dg": 6e-2,
                  "dbeta": 2e-2}


def scan_against_recurrence(seq: int) -> dict:
    """``kda_attention`` forward and backward (on the chip: the forward
    rule's ``kda_fwd_states``, then ``kda_bwd_grads``) with decays from none
    down to the floor ``G_MIN``, against ``jax.vjp`` of ``kda_recurrent``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import kda

    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    shape = (1, seq, 4, 128)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q, k = (unit(jax.random.normal(ks[i], shape)) for i in (0, 1))
    v, do = (jax.random.normal(ks[i], shape) for i in (2, 3))
    g = jax.random.uniform(ks[4], shape, minval=kda.G_MIN, maxval=-1e-3)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], shape[:3]))
    bf = lambda a: a.astype(jnp.bfloat16)
    got, vjp = jax.vjp(kda.kda_attention, bf(q), bf(k), bf(v), g, beta)
    with jax.default_matmul_precision("highest"):
        want, want_vjp = jax.vjp(kda.kda_recurrent, q, k, v, g, beta)
        wants = (want,) + want_vjp(do)
    rel = {n: float(jnp.linalg.norm(a.astype(jnp.float32) - w)
                    / jnp.linalg.norm(w))
           for n, a, w in zip(TOL_SOLAR_SCAN, (got,) + vjp(bf(do)), wants)}
    for n, r in rel.items():
        check(r <= TOL_SOLAR_SCAN[n],
              f"solar: the scan's {n} is {r:.2e} from the recurrence's "
              f"(tol {TOL_SOLAR_SCAN[n]})")
    return rel


# what lies between the projections and the scan (ops/kda_prep.py), bf16,
# operands and the six gradients against the jax.numpy spelling, ||difference||
# / ||reference||: the spelling rounds to bf16 after the SiLU and again after
# the norm, the kernel once (seen on the v5e, PR 41: 2.7e-3 to 2.9e-3)
TOL_SOLAR_PREP = 1e-2


def prep_against_spelling(shape, path: str) -> dict:
    """``kda_prep`` by the path observed here (on the chip: the Pallas pair
    ``kda_prep``/``kda_prep_bwd``) against ``xla_kda_prep``, forward and
    ``jax.vjp`` towards the three projections and the three tap matrices;
    ``shape`` [b, s, heads * 128]. The worst relative distance."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import profiler
    from paddle_tpu.ops import kda_prep as kp

    ks = jax.random.split(jax.random.PRNGKey(5), 9)
    bf = lambda a: a.astype(jnp.bfloat16)
    ps = tuple(bf(jax.random.normal(k, shape)) for k in ks[:3])
    ws = tuple(bf(jax.random.uniform(k, (4, shape[2]), minval=-0.5,
                                     maxval=0.5)) for k in ks[3:6])
    cs = tuple(bf(jax.random.normal(k, shape)) for k in ks[6:])

    def both_ways(chain):
        def run(ps, ws, cs):
            out, vjp = jax.vjp(
                lambda p, w: chain(p, w, (True, True, False), 128, 1e-6),
                ps, ws)
            return out, vjp(cs)
        return jax.tree.leaves(jax.jit(run)(ps, ws, cs))

    profiler.reset()
    got = both_ways(kp.kda_prep)
    took = sorted(k for k in profiler.summary()["metrics"]
                  if k.startswith("kda/prep_calls"))
    check(took == ["kda/prep_calls{path=%s}" % path],
          f"solar: the chain before the scan took {took}, not the {path} "
          "path")
    names = ("q", "k", "v", "dp_q", "dp_k", "dp_v", "dconv_q", "dconv_k",
             "dconv_v")
    rel = {}
    for n, a, w in zip(names, got, both_ways(kp.xla_kda_prep)):
        w = w.astype(jnp.float32)
        rel[n] = float(jnp.linalg.norm(a.astype(jnp.float32) - w)
                       / jnp.linalg.norm(w))
        check(rel[n] <= TOL_SOLAR_PREP,
              f"solar: the chain's {n} is {rel[n]:.2e} from the spelling's "
              f"(tol {TOL_SOLAR_PREP})")
    return rel


def phase_solar(seq: int, scan_path: str, prep_shape) -> dict:
    """One step at learning rate 0 of ``SolarOpen2Config.tiny`` (heads of
    128, so the Pallas scan takes them) with experts 4..11 of 16 held;
    the scan alone against the recurrence; the chain between the
    projections and the scan at ``prep_shape`` against its spelling."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh
    from paddle_tpu.models import solar_open2_reference as ref
    from paddle_tpu.models.solar_open2 import SolarOpen2, SolarOpen2Config
    from paddle_tpu.static.functional import state_tensors

    held = (4, 8)
    paddle.seed(7)
    cfg = SolarOpen2Config.tiny(experts_held=held)
    model = SolarOpen2(cfg)
    layers = [{k: np.asarray(v._value) for k, v in
               zip(*state_tensors(layer)[:2])}
              for period in model.periods for layer in period.layers]
    names, tensors = state_tensors(model)[:2]
    other = {n: np.asarray(t._value) for n, t in zip(names, tensors)
             if not n.startswith("periods.")}
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, seq),
                                               dtype=np.int32)
    rc = dict(heads=cfg.num_attention_heads,
              kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
              linear_heads=cfg.linear_attn_num_heads,
              linear_head_dim=cfg.linear_attn_head_dim,
              top_k=cfg.num_experts_per_tok, eps=cfg.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        want = float(np.mean([ref.loss(layers, other, tokens[i:i + 1], rc,
                                       held) for i in range(2)]))
    s = DistributedStrategy()
    s.amp = True
    mesh = create_mesh({"dp": 1, "pp": 1, "tp": 1, "sp": 1},
                       jax.devices()[:1])
    profiler.reset()
    tr = HybridPipelineTrainer(
        model, paddle.optimizer.SGD(0.0, parameters=model.parameters()), s,
        mesh, n_micro=2, param_dtype="bfloat16")
    got = float(tr.step(tokens))
    stats = jax.device_get(tr.aux_stats)
    calls = {k: v["value"] for k, v in profiler.summary()["metrics"].items()
             if k.startswith(("kda/scan_calls", "kda/prep_calls"))}
    rel = abs(got - want) / abs(want)
    check(rel <= TOL_SOLAR_LOSS, f"solar: the trainer's loss {got:.5f} is "
          f"{rel:.2e} from the reference's {want:.5f}")
    check(set(calls) == {"kda/scan_calls{path=%s}" % scan_path,
                         "kda/prep_calls{path=%s}" % scan_path},
          f"solar: the layer took {sorted(calls)}, not the {scan_path} path")
    check(stats["moe/routed"] == tokens.size * cfg.num_experts_per_tok * 4
          and stats["moe/assigned"] == stats["moe/rows"].sum(),
          f"solar: the step's counts do not add up: {stats}")
    say("solar", f"tiny Solar-Open2, 2 x {seq} tokens, bf16: loss {got:.5f}"
        f" vs float32 reference {want:.5f} (rel {rel:.2e}, tol "
        f"{TOL_SOLAR_LOSS}); scan through {scan_path}; rows held "
        f"{int(stats['moe/assigned'])} of {int(stats['moe/routed'])} routed")
    scan = scan_against_recurrence(seq)
    say("solar", "the scan forward and backward against the recurrence, g "
        "down to its floor: " + ", ".join(
            f"{n} {r:.1e}" for n, r in scan.items()))
    prep = prep_against_spelling(prep_shape, scan_path)
    say("solar", f"the chain between the projections and the scan at "
        f"{list(prep_shape)} bf16 through {scan_path}, against its spelling: "
        + ", ".join(f"{n} {r:.1e}" for n, r in prep.items()))
    return {"loss": got, "rel": rel, "scan": scan, "prep": prep}


# ---------------------------------------------------------------------------
def main() -> int:
    from paddle_tpu.utils.compile_cache import (cache_entries,
                                                enable_compile_cache)

    cache_dir = enable_compile_cache()
    t_start = time.perf_counter()
    device = phase_device(cache_dir)         # exits when there is no TPU

    import jax

    from paddle_tpu.models import GPTConfig

    cfg = GPTConfig.gpt3_1_3b()
    heads, head_dim = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    page, slots = 16, 8
    failed, seen = [], {}

    def run(name, fn):
        t0 = time.perf_counter()
        say(name, f"start, {_gb(memory_stat('bytes_in_use'))} in use")
        try:
            seen[name] = fn()
        except Exception:       # reported, and carried by the exit code
            traceback.print_exc()
            failed.append(name)
            say(name, "FAILED")
        release_device_memory()
        say(name, f"phase wall {time.perf_counter() - t0:.1f} s")

    run("kernels", lambda: phase_kernels(
        (2, cfg.max_seq_len, heads, head_dim), page, heads, head_dim))
    olmoe = GPTConfig.olmoe_1b_7b()
    run("experts", lambda: phase_experts(
        olmoe.max_seq_len, olmoe.hidden_size, olmoe.moe_expert_width,
        olmoe.moe_num_experts, olmoe.moe_top_k))
    # the chain at the training cell's shapes: 8,192 tokens, 64 heads of 128
    run("solar", lambda: phase_solar(256, "pallas", (1, 8192, 8192)))
    run("head", lambda: phase_head(HEAD_SHAPES))
    run("serve", lambda: phase_serve(cfg, slots, page, SERVE_REQUESTS))
    # the looped model at its published widths, 3 of its 48 layers
    looped = dataclasses.replace(GPTConfig.ouro_2_6b(), num_layers=3)
    run("serve-looped", lambda: phase_serve_looped(looped, 4, page, 16))
    # the latent-attention model: its pools' reads at the cell's row shapes
    # (128 heads over latents of 576, pages of 128: a chunk row of 256 and
    # twelve decode rows), and a small model of its kinds of layer through
    # the engine
    from paddle_tpu.models.dots3 import Dots3Config

    widths = (128, 576, 512, 128, 24, 512, 513, jax.numpy.bfloat16)
    run("latent", lambda: phase_latent(
        Dots3Config.tiny(hidden_size=256, experts_held=(0, 4)), 3, 4, 24,
        [(1, 256) + widths, (12, 1) + widths]))
    # DeepSeek-V2: dense latent attention at its cell's row shapes (two
    # chunk rows of 256, twenty decode rows), and a small model through the
    # engine, two chunks a tick
    from paddle_tpu.models.deepseek_v2 import DeepseekV2Config

    dense = (128, 576, 512, 128, 24, jax.numpy.bfloat16)
    run("dsv2", lambda: phase_dsv2(
        DeepseekV2Config.tiny(hidden_size=256, experts_held=(4, 4)), 3, 4,
        24, [(2, 256) + dense, (20, 1) + dense]))
    # Olmo-Hybrid: the served delta rule's kernels at its heads (30 of
    # 96 x 192, the cell's 40 decode rows and chunk of 256), and a small
    # model of both kinds of layer through the engine, whose heads are of
    # those sizes too and whose eight decode rows fill a sublane tile, so
    # that its ticks take the kernels
    from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig

    run("olmoh", lambda: phase_olmoh(
        OlmoHybridConfig(
            vocab_size=1024, hidden_size=512, intermediate_size=1024,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=4, linear_num_key_heads=4,
            linear_num_value_heads=4, max_position_embeddings=512),
        8, 16, 32, 128, (30, 96, 192, 40, 256), "pallas"))
    # Ling-3.0: the served per-channel rule's kernels at its heads (32 of
    # 128 x 128, the cell's 64 decode rows and chunk of 256), and a small
    # model (a dense layer, two KDA layers, an MLA layer; 16 heads of 128,
    # latent rows of 128 + 64 in pages of 128, eight decode rows) through
    # the engine, whose ticks must take the kernels for the state step and
    # for the latent attention
    from paddle_tpu.models.ling3 import Ling3Config

    run("ling", lambda: phase_ling(
        Ling3Config(
            vocab_size=1024, hidden_size=512, intermediate_size=1024,
            moe_intermediate_size=128,
            moe_shared_expert_intermediate_size=128, num_hidden_layers=4,
            layer_ids=(1, 3, 4, 5), num_attention_heads=16, kv_lora_rank=128,
            num_experts=16, n_group=4, topk_group=2, num_experts_per_tok=4,
            experts_held=(4, 8), select_bias_range=0.02,
            max_position_embeddings=1024),
        8, 128, 8, 256, (32, 128, 128, 64, 256), "pallas"))
    # Falcon-H1: the served state-space rule's kernels at its heads (32 of
    # 128 x 256 in 2 groups, the cell's 80 decode rows and chunk of 256),
    # grouped-query attention at the cell's rows (80 decode rows at 660 live
    # positions, a chunk row's piece of 64 queries; 20 heads over 4, pages of
    # 16), and a small model (8 SSD heads of 128 x 256, a convolution over
    # 2,048 channels, 20 query heads over 4, eight decode rows, chunks of one
    # SSD block) through the engine, whose ticks must take every kernel
    from paddle_tpu.models.falcon_h1 import FalconH1Config

    run("falcon", lambda: phase_falcon(
        FalconH1Config(
            vocab_size=1024, hidden_size=512, intermediate_size=1024,
            num_hidden_layers=3, mamba_d_ssm=1024, mamba_n_heads=8,
            max_position_embeddings=1024),
        8, 16, 64, 128, (32, 128, 256, 2, 80, 256),
        [(80, 1, 20, 4, 128, 16, 88, 660), (4, 64, 20, 4, 128, 16, 88, 512)],
        "pallas",
        ragged=[(80, 1, 20, 4, 128, 16, 88), (16, 64, 20, 4, 128, 16, 88)]))
    # Laguna: the windowed grouped attention at the cell's rows (38 decode
    # rows 7,000 deep and a chunk row's piece of 32 queries, 8 key/value
    # heads, six and nine query heads each, pages of 16, a window of 512),
    # and a small model (12 and 18 query heads over 2 key/value heads of
    # 128, a window of 64, 8 of 16 experts held) through the engine, whose
    # ticks must take both kernels
    from paddle_tpu.models.laguna import FULL, SLIDING, LagunaConfig

    run("laguna", lambda: phase_laguna(
        LagunaConfig(
            vocab_size=1024, hidden_size=512, intermediate_size=1024,
            num_hidden_layers=3, layer_types=(FULL, SLIDING, SLIDING),
            num_attention_heads=12, num_key_value_heads=2,
            num_attention_heads_per_layer=(12, 18, 18), num_experts=16,
            num_experts_per_tok=4, moe_intermediate_size=128,
            shared_expert_intermediate_size=128, sliding_window=64,
            experts_held=(4, 8), max_position_embeddings=1024),
        8, 16, 64, 128,
        [(38, 1, 48, 8, 128, 16, 448, 7000, 512),
         (38, 1, 72, 8, 128, 16, 448, 7000, 512),
         (8, 32, 48, 8, 128, 16, 128, 1500, 512),
         (8, 32, 72, 8, 128, 16, 128, 1500, 512)], "pallas",
        ragged=[(38, 1, 48, 8, 128, 16, 448), (38, 1, 72, 8, 128, 16, 448, 512),
                (16, 32, 48, 8, 128, 16, 128),
                (16, 32, 72, 8, 128, 16, 128, 512)]))
    run("train", lambda: phase_train(cfg, micro=2, n_micro=6, steps=4))
    if len(jax.devices()) >= 4 and "train" not in failed:
        run("multichip", lambda: phase_multichip(
            cfg, 2, 6, seen["train"]["loss0"],
            GPTConfig(vocab_size=512, hidden_size=512, num_layers=4,
                      num_heads=4, max_seq_len=128), zero_batch=8,
            # 6.7B's head on pp2 x tp2: the whole vocabulary, two shards
            head=(48, 2048, 4096, 50304, True, jax.numpy.bfloat16)))
    else:
        say("multichip", f"{len(jax.devices())} device, not run"
            if len(jax.devices()) < 4 else "not run: train failed")

    say("done", f"total {time.perf_counter() - t_start:.1f} s, compile "
        f"cache now {cache_entries(cache_dir)} entries, peak "
        f"{_gb(peak_bytes())}; smoke observations, not benchmark results")
    print(json.dumps({"ok": not failed, "device": device,
                      **({"failed": failed} if failed else {})}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
