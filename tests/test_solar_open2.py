"""Solar-Open2 (models/solar_open2.py): the chunked gated delta rule
(ops/kda.py) against the token-by-token recurrence, grouped-query flash
attention against the unfused reference, the expert layer that holds a share
of its experts (distributed/moe.py), and the period and the whole model
through HybridPipelineTrainer against the plain float32 reference
(models/solar_open2_reference.py); small sizes on the CPU, float32, seeded
weights, matmuls at ``highest`` (tests/conftest.py)."""
import functools
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.distributed.moe import (DroplessMoEMLP, HeldMoEMLP,
                                        held_moe, held_window_rows)
from paddle_tpu.models import GPT
from paddle_tpu.models import solar_open2 as prog
from paddle_tpu.models import solar_open2_reference as ref
from paddle_tpu.models.solar_open2 import SolarOpen2, SolarOpen2Config
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import kda
from paddle_tpu.ops import kda_prep
from paddle_tpu.static.functional import state_tensors

HELD = (4, 8)          # experts 4..11 of the small model's 16


# --- the scan ---------------------------------------------------------------
def scan_inputs(seed, b, s, h, d, decay, beta):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, s, h, d)))
    k = unit(jax.random.normal(ks[1], (b, s, h, d)))
    v = jax.random.normal(ks[2], (b, s, h, d))
    g = jnp.log(jax.random.uniform(ks[3], (b, s, h, d), minval=decay[0],
                                   maxval=decay[1]))
    logit = {"near0": -6.0, "near2": 6.0, "spread": 0.0}[beta] \
        + jax.random.normal(ks[4], (b, s, h)) * (3.0 if beta == "spread"
                                                 else 0.5)
    return (q, k, v, g, 2 * jax.nn.sigmoid(logit)), \
        jax.random.normal(ks[5], (b, s, h, d))


def against_the_recurrence(fn, args, do, tol):
    """Outputs and the gradients of all five inputs, each within ``tol``
    of its largest entry."""
    want, want_vjp = jax.vjp(kda.kda_recurrent, *args)
    got, got_vjp = jax.vjp(fn, *args)
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    for name, a, w in zip(names, (got,) + got_vjp(do),
                          (want,) + want_vjp(do)):
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=0,
                                   atol=tol * scale, err_msg=name)


@pytest.mark.parametrize("seq", [128, 100])
@pytest.mark.parametrize("decay", [(0.99, 0.9999), (0.45, 0.6)],
                         ids=["decay-near-1", "decay-near-half"])
@pytest.mark.parametrize("beta", ["near0", "near2", "spread"])
def test_chunked_scan_equals_the_recurrence(seq, decay, beta):
    # float32 on both sides, sums in another order: 2e-5 of the largest
    args, do = scan_inputs(seq, 2, seq, 2, 64, decay, beta)
    against_the_recurrence(kda.kda_attention, args, do, 2e-5)


def test_decays_far_below_a_half_stay_exact():
    """exp(g) down to 0.01 a token: 1e-128 over a chunk, which no
    ``exp(-cumsum g)`` could carry; the sub-chunks' relative decays do."""
    args, do = scan_inputs(7, 1, 128, 2, 64, (0.01, 0.6), "spread")
    against_the_recurrence(kda.kda_attention, args, do, 2e-5)


def steep_inputs(seed, lo, hi, d=64):
    """Inputs whose ``g`` is uniform in [lo, hi], a token and channel."""
    (q, k, v, _, beta), do = scan_inputs(seed, 1, 128, 2, d, (0.5, 0.9),
                                         "spread")
    g = jax.random.uniform(jax.random.PRNGKey(seed + 100), q.shape,
                           minval=lo, maxval=hi)
    return (q, k, v, g, beta), do


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_decays_down_to_the_floor_stay_exact(path):
    """``g`` anywhere from 0 down to ``G_MIN``, a token and channel: 8
    tokens at the floor are ``exp(72)`` inside a sub-chunk, which float32
    holds; the kernels and the ``jax.numpy`` path alike."""
    d = 128 if path == "pallas" else 64
    args, do = steep_inputs(13, kda.G_MIN, -1e-3, d)
    against_the_recurrence(kernels if path == "pallas"
                           else kda.kda_attention, args, do, 2e-5)


def test_past_the_floor_the_decay_is_held_at_it():
    """``g`` down to -60 a token (``exp(-15 x 60)`` inside a sub-chunk,
    which nothing holds): the scan computes the recurrence with ``g`` at
    ``G_MIN``, finite everywhere, within the floor's own 1.2e-4 of the
    recurrence as asked, and ``g`` takes no gradient where it was held."""
    (q, k, v, g, beta), do = steep_inputs(17, -60.0, -0.01)
    held = jnp.maximum(g, kda.G_MIN)
    got, vjp = jax.vjp(kda.kda_attention, q, k, v, g, beta)
    want, want_vjp = jax.vjp(kda.kda_recurrent, q, k, v, held, beta)
    grads, want_grads = vjp(do), list(want_vjp(do))
    want_grads[3] = jnp.where(g < kda.G_MIN, 0.0, want_grads[3])
    for a, w in zip((got,) + grads, [want] + want_grads):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, w, rtol=0,
                                   atol=1e-4 * float(jnp.abs(w).max()))
    assert float(jnp.abs(jnp.where(g < kda.G_MIN, grads[3], 0.0)).max()) == 0
    asked = kda.kda_recurrent(q, k, v, g, beta)
    assert float(jnp.abs(got - asked).max()) \
        < 3 * np.exp(kda.G_MIN) * float(jnp.abs(asked).max())


def test_neighbouring_keys_alike_and_beta_near_two_stay_exact():
    """What the model gives the scan and random keys do not: keys that a
    short convolution makes alike from one token to the next, with beta
    near 2. ``(I + beta M)`` then has entries near 2 below its diagonal;
    its inverse by blocks holds, the closed product of powers did not
    (seen on the chip: NaN at token 2,880 of one head of 64)."""
    (q, k, v, g, beta), do = scan_inputs(11, 1, 256, 1, 64, (0.99, 0.9999),
                                         "near2")
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    drift = jnp.cumsum(k, axis=1) * 0.02 + k[:, :1]
    k = unit(drift + 0.05 * k)
    assert float(jnp.sum(k[:, 1:] * k[:, :-1], -1).min()) > 0.9
    against_the_recurrence(kda.kda_attention, (q, k, v, g, beta), do, 1e-4)


def flat(a):
    return a.reshape(a.shape[0], a.shape[1], -1)


def kernels(q, k, v, g, beta):
    """``pallas_kda`` (interpreted here) on ``kda_attention``'s layout."""
    return kda.pallas_kda(flat(q), flat(k), flat(v), flat(g), beta,
                          q.shape[-1] ** -0.5).reshape(v.shape)


def test_pallas_scan_kernels_equal_the_recurrence():
    """The kernels themselves, interpreted: the forward rule's sweep and
    the backward sweep."""
    args, do = scan_inputs(1, 1, 128, 2, 128, (0.5, 0.999), "spread")
    against_the_recurrence(kernels, args, do, 2e-5)


def test_the_backward_pass_is_one_sweep():
    """``jax.grad`` through ``pallas_kda`` holds the forward rule's sweep
    and one backward kernel: nothing rebuilds the chunks' entry states."""
    args, do = scan_inputs(2, 1, 192, 2, 128, (0.5, 0.999), "spread")
    loss = lambda *a: jnp.sum(kernels(*a) * do)
    names = pallas_call_names(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args).jaxpr)
    assert names == ["kda_fwd_states", "kda_bwd_grads"], names
    assert pallas_call_names(jax.make_jaxpr(kernels)(*args).jaxpr) \
        == ["kda_fwd"]


def test_under_a_checkpoint_the_gradients_are_the_same_bits():
    """The layer's ``jax.checkpoint``: the residuals are born in the
    recomputed forward sweep; still one backward kernel, and every
    gradient equals the unchecked one bit for bit."""
    args, do = scan_inputs(3, 1, 192, 2, 128, (0.5, 0.999), "spread")
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * do)
    grad = lambda fn: jax.grad(loss(fn), argnums=(0, 1, 2, 3, 4))
    names = pallas_call_names(
        jax.make_jaxpr(grad(jax.checkpoint(kernels)))(*args).jaxpr)
    assert [n for n in names if n.startswith("kda_bwd")] \
        == ["kda_bwd_grads"], names
    assert "kda_fwd_states" in names
    for a, b in zip(jax.jit(grad(jax.checkpoint(kernels)))(*args),
                    jax.jit(grad(kernels))(*args)):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def recurrent_states(q, k, v, g, beta):
    """``kda_recurrent``'s carry before every token, [s, b, h, dk, dv]."""
    f = lambda a: jnp.moveaxis(a, 1, 0)
    hi = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    def step(state, x):
        kt, vt, gt, bt = x
        new = state * jnp.exp(gt)[..., None]
        err = vt - hi("bhk,bhkv->bhv", kt, new)
        return new + hi("bhk,bhv->bhkv", kt * bt[..., None], err), state

    zero = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:], jnp.float32)
    return jax.lax.scan(step, zero, (f(k), f(v), f(g), f(beta)))[1]


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_the_forward_rule_leaves_the_recurrences_carry_at_every_chunk_edge(
        path):
    """What the backward sweep reads in place of a sweep of its own: the
    state before every chunk, float32, and (the second residual) each
    chunk's unit lower-triangular inverse."""
    args, _ = scan_inputs(5, 1, 256, 2, 128, (0.5, 0.999), "spread")
    q, k, v, g, beta = args
    rule = {"xla": kda._xla_kda_fwd, "pallas": kda._pallas_kda_fwd}[path]
    o, res = rule(flat(q), flat(k), flat(v), flat(g), beta, 128 ** -0.5)
    states, invs = res[5:]
    if path == "xla":                   # [chunks, b, h, ...] -> [b, h, chunks]
        states, invs = (jnp.moveaxis(a, 0, 2) for a in (states, invs))
    want = jnp.transpose(recurrent_states(*args)[::kda.CHUNK],
                         (1, 2, 0, 3, 4))
    assert states.dtype == jnp.float32 and states.shape == want.shape
    assert float(jnp.abs(want[:, :, 1:]).max()) > 0.1
    np.testing.assert_allclose(np.asarray(states), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))
    want_o = kda.kda_recurrent(*args)
    np.testing.assert_allclose(
        np.asarray(o.reshape(v.shape)), np.asarray(want_o), rtol=0,
        atol=2e-5 * float(jnp.abs(want_o).max()))
    c = kda.CHUNK
    assert invs.shape == (1, 2, 256 // c, c, c)
    upper = np.triu(np.ones((c, c), bool), 1)
    assert float(jnp.abs(jnp.where(upper, invs, 0.0)).max()) == 0
    np.testing.assert_array_equal(
        np.asarray(jnp.diagonal(invs, axis1=-2, axis2=-1)), 1.0)


def test_the_scans_path_is_observed_and_counted():
    assert kda.kernel_path(8192, 128, 128) == "xla"     # the CPU
    profiler.reset()
    args, _ = scan_inputs(0, 1, 64, 1, 64, (0.5, 0.999), "spread")
    jax.jit(kda.kda_attention)(*args)
    seen = profiler.summary()["metrics"]
    assert seen["kda/scan_calls{path=xla}"]["value"] == 1
    assert "kda/scan_calls{path=pallas}" not in seen


# --- between the projections and the scan (ops/kda_prep.py) ---------------------
NORMS = (True, True, False)


def prep_inputs(seed, b, s, width, dtype, cot_rows=None):
    """Three projections, their taps and three cotangents (zero below row
    ``cot_rows`` on, where given)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    ps = tuple(jax.random.normal(k, (b, s, width)).astype(dtype)
               for k in ks[:3])
    ws = tuple(jax.random.uniform(k, (4, width), minval=-0.5,
                                  maxval=0.5).astype(dtype)
               for k in ks[3:6])
    cs = tuple(jax.random.normal(k, (b, s, width)) for k in ks[6:])
    if cot_rows is not None:
        cs = tuple(c.at[:, cot_rows:].set(0.0) for c in cs)
    return ps, ws, tuple(c.astype(dtype) for c in cs)


def prep_both_ways(chain, ps, ws, cs, norms=NORMS):
    """Outputs, gradients towards the projections and towards the taps."""
    out, vjp = jax.vjp(lambda p, w: chain(p, w, norms, 128, prog.L2_EPS),
                       ps, ws)
    return jax.tree.leaves((out, vjp(cs)))


#: (sequence, width, batch, norms, cotangent's rows, rows a block): one row
#: block and two column tiles; three blocks of 176 rows, so the history
#: crosses a block's edge forward and the rows after it backward; 17 blocks
#: of one tile's 16 rows over two sequences (the tap sums over batch and
#: row blocks); cotangents on the first three rows alone, whose history is
#: zeros; no branch with a norm
PREP_CASES = {
    "one-block": (64, 256, 1, NORMS, None, 2048),
    "three-blocks": (528, 256, 1, NORMS, None, 176),
    "blocks-of-a-tile": (272, 128, 2, NORMS, None, 16),
    "first-three-rows": (512, 256, 1, NORMS, 3, 256),
    "no-norm": (512, 128, 1, (False, False, False), None, 256),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(PREP_CASES))
def test_prep_kernels_equal_the_spelling(monkeypatch, case, dtype):
    """``kda_prep``/``kda_prep_bwd`` interpreted against the ``jax.numpy``
    chain: the operands and the gradients towards the three projections
    and the three tap matrices. float32: the same arithmetic in another
    order. bf16: the spelling rounds after the SiLU and after the norm and
    the kernel once, so they differ by the spelling's own rounding."""
    s, width, b, norms, cot_rows, rows = PREP_CASES[case]
    monkeypatch.setattr(kda_prep, "_ROWS", rows)    # the most rows a block
    assert kda_prep._blocks((b, s, width), 128)[:2] == (min(rows, s), 128)
    ps, ws, cs = prep_inputs(len(case), b, s, width, dtype, cot_rows)
    got = prep_both_ways(kda_prep.pallas_kda_prep, ps, ws, cs, norms)
    want = prep_both_ways(kda_prep.xla_kda_prep, ps, ws, cs, norms)
    assert len(got) == len(want) == 9
    for a, w in zip(got, want):
        assert a.shape == w.shape and a.dtype == w.dtype
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        if dtype == "float32":
            np.testing.assert_allclose(a, w, rtol=0,
                                       atol=1e-5 * np.abs(w).max())
        else:
            assert np.linalg.norm(a - w) <= 6e-3 * np.linalg.norm(w)
            np.testing.assert_allclose(a, w, rtol=0,
                                       atol=2.0 ** -6 * np.abs(w).max())
    if cot_rows is not None:
        # what the first rows' cotangents reach: themselves and no row after
        assert all(float(jnp.abs(d[:, cot_rows:]).max()) == 0
                   for d in got[3:6])
        assert all(float(jnp.abs(d[:, :cot_rows]).max()) > 0
                   for d in got[3:6])


def kda_layer_weights(c, seed):
    layer = prog.SolarKDA(c)
    ks = jax.random.split(jax.random.PRNGKey(seed), 32)
    return {n: jax.random.normal(k, p.shape) * (0.3 if n.startswith("conv")
                                               else 0.05)
            for k, (n, p) in zip(ks, layer.named_parameters())}


def test_the_layer_through_the_kernels_is_the_layer_through_the_spelling(
        monkeypatch):
    """``kda_mix`` at a small width, output and every gradient, with the
    chain forced through the interpreted kernels against the path the CPU
    observes."""
    c = SolarOpen2Config.tiny()
    w = kda_layer_weights(c, 3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, c.hidden_size))
    do = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def both_ways():
        out, vjp = jax.vjp(lambda x, w: prog.kda_mix(x, w, c), x, w)
        return out, vjp(do)

    profiler.reset()
    want = both_ways()
    monkeypatch.setattr(kda_prep, "prep_path", lambda *a: "pallas")
    got = both_ways()
    seen = profiler.summary()["metrics"]
    assert seen["kda/prep_calls{path=xla}"]["value"] == 1
    assert seen["kda/prep_calls{path=pallas}"]["value"] == 1
    flat = lambda t: jax.tree_util.tree_leaves_with_path(t)
    for (path, a), (_, b) in zip(flat(got), flat(want)):
        np.testing.assert_allclose(
            a, b, rtol=0, atol=2e-5 * float(jnp.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))


def test_the_chains_path_is_observed_and_counted():
    assert kda_prep.prep_path(8192, 128, 4) == "xla"     # the CPU
    profiler.reset()
    ps, ws, _ = prep_inputs(0, 1, 32, 128, "float32")
    jax.jit(lambda p, w: kda_prep.kda_prep(p, w, NORMS, 128, 1e-6))(ps, ws)
    seen = profiler.summary()["metrics"]
    assert seen["kda/prep_calls{path=xla}"]["value"] == 1
    assert "kda/prep_calls{path=pallas}" not in seen


def pallas_call_names(jaxpr):
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += pallas_call_names(sub)
    return names


def test_the_chains_kernels_are_not_counted_as_the_scan():
    """perfbench/layer_metrics/_kda_trace.py takes every Mosaic call whose
    name starts with ``kda_fwd`` or ``kda_bwd`` for the scan."""
    ps, ws, cs = prep_inputs(0, 1, 32, 128, "float32")
    names = pallas_call_names(jax.make_jaxpr(
        lambda p, w, c: prep_both_ways(kda_prep.pallas_kda_prep, p, w, c))(
            ps, ws, cs).jaxpr)
    assert sorted(names) == ["kda_prep", "kda_prep_bwd"]
    assert not [n for n in names if n.startswith(("kda_fwd", "kda_bwd"))]


# --- grouped-query flash attention ---------------------------------------------
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [1, 3])
def test_flash_with_fewer_key_value_heads(monkeypatch, causal, blocks):
    monkeypatch.setattr(fa, "_BLOCK_Q", 128)
    monkeypatch.setattr(fa, "_BLOCK_K", 128)
    b, s, h, kv, d = 2, 128 * blocks, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    do = jax.random.normal(ks[3], (b, s, h, d))
    assert fa.supported(q.shape, None, 0.0, kv_seq=s, kv_heads=kv)
    assert not fa.supported(q.shape, None, 0.0, kv_seq=s, kv_heads=3)
    got, got_vjp = jax.vjp(
        lambda *a: fa.flash_mha(*a, causal=causal), q, k, v)
    # repeated heads, spelled out here and not by the function under test
    rep = lambda a: jnp.repeat(a, h // kv, axis=2)
    want, want_vjp = jax.vjp(
        lambda q_, k_, v_: fa.mha_reference(q_, rep(k_), rep(v_), causal),
        q, k, v)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    for a, w in zip(got_vjp(do), want_vjp(do)):
        assert a.shape == w.shape
        np.testing.assert_allclose(a, w, rtol=0, atol=5e-5)
    np.testing.assert_allclose(fa.mha_reference(q, k, v, causal), want,
                               rtol=0, atol=1e-6)


# --- the expert layer that holds a share ---------------------------------------
def expert_weights(seed, h=32, f=16, e=16):
    r = np.random.default_rng(seed)
    n = lambda *shape: jnp.asarray(r.normal(size=shape).astype(np.float32))
    return {"mlp.gate": n(h, e), "mlp.select_bias": 0.05 * n(e),
            "mlp.w_gate": 0.3 * n(e, h, f), "mlp.w_up": 0.3 * n(e, h, f),
            "mlp.w_down": 0.3 * n(e, f, h), "mlp.shared_gate": 0.3 * n(h, f),
            "mlp.shared_up": 0.3 * n(h, f), "mlp.shared_down": 0.3 * n(f, h)}


def share(w, first, count):
    held = {k: v[first:first + count] if k in (
        "mlp.w_gate", "mlp.w_up", "mlp.w_down") else v for k, v in w.items()}
    return held


def program_share(x, w, first, count, top_k, shared):
    held = share(w, first, count)
    return held_moe(
        x, held["mlp.gate"], held["mlp.w_gate"], held["mlp.w_up"],
        held["mlp.w_down"], top_k, (first, count),
        select_bias=held["mlp.select_bias"],
        shared=tuple(held["mlp.shared_" + n] for n in ("gate", "up", "down"))
        if shared else None)


def test_four_shares_and_the_shared_expert_once_are_the_whole_layer():
    """The share test: at 16 experts, the routed parts of four shares of 4
    plus the shared expert counted once equal the uncut reference layer."""
    w, top_k = expert_weights(3), 4
    x = jnp.asarray(np.random.default_rng(4).normal(size=(256, 32)),
                    jnp.float32)
    whole = ref.moe(x, w, {"top_k": top_k})
    parts, rows = [], []
    for first in range(0, 16, 4):
        y, r = program_share(x, w, first, 4, top_k, shared=first == 0)
        parts.append(y)
        rows.append(np.asarray(r))
        want, want_rows, _ = ref.moe(x, share(w, first, 4), {"top_k": top_k},
                                     held=(first, 4), with_routing=True,
                                     shared=first == 0)
        np.testing.assert_allclose(y, want, rtol=0,
                                   atol=1e-5 * float(jnp.abs(want).max()))
        np.testing.assert_array_equal(rows[-1], np.asarray(want_rows))
    assert np.concatenate(rows).sum() == 256 * top_k      # none lost
    np.testing.assert_allclose(sum(parts), whole, rtol=0,
                               atol=2e-5 * float(jnp.abs(whole).max()))


@pytest.mark.parametrize("what", ["forward", "gradients"])
def test_a_routing_that_sends_everything_here_loses_nothing(what):
    """Adversarial: the selection bias sends every token's every choice to
    the held experts, four times what a window of the rows takes; the
    windows go round four times and the result is the reference's."""
    w, top_k, t = expert_weights(5), 4, 256
    w["mlp.select_bias"] = jnp.where(
        (jnp.arange(16) >= 4) & (jnp.arange(16) < 8), 5.0, 0.0)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(t, 32)),
                    jnp.float32)
    assert held_window_rows(t, top_k, 4, 16) * 2 <= t * top_k
    program = lambda x_, w_: program_share(x_, w_, 4, 4, top_k, True)
    reference = lambda x_, w_: ref.moe(x_, share(w_, 4, 4), {"top_k": top_k},
                                       held=(4, 4))
    if what == "forward":
        y, rows = program(x, w)
        assert int(rows.sum()) == t * top_k
        want = reference(x, w)
        np.testing.assert_allclose(y, want, rtol=0,
                                   atol=1e-5 * float(jnp.abs(want).max()))
        return
    cot = jnp.asarray(np.random.default_rng(7).normal(size=(t, 32)),
                      jnp.float32)
    got = jax.grad(lambda x_, w_: jnp.sum(program(x_, w_)[0] * cot),
                   argnums=(0, 1))(x, w)
    want = jax.grad(lambda x_, w_: jnp.sum(reference(x_, w_) * cot),
                    argnums=(0, 1))(x, w)
    np.testing.assert_allclose(got[0], want[0], rtol=0,
                               atol=2e-5 * float(jnp.abs(want[0]).max()))
    for name in ("mlp.gate", "mlp.w_gate", "mlp.w_up", "mlp.w_down",
                 "mlp.shared_down"):
        g, wg = got[1][name], want[1][name]
        if name in ("mlp.w_gate", "mlp.w_up", "mlp.w_down"):
            g, wg = g[4:8], wg[4:8]       # the reference was given a share
        np.testing.assert_allclose(
            g, wg, rtol=0, atol=2e-5 * float(jnp.abs(wg).max()),
            err_msg=name)


def test_the_plain_layer_is_told_nothing_new():
    """``DroplessMoEMLP`` and ``dropless_moe`` keep the parent's arguments:
    the layer that holds a share is their sibling, not an option."""
    import inspect

    from paddle_tpu.distributed.moe import dropless_moe
    assert list(inspect.signature(dropless_moe).parameters) == [
        "x", "router_w", "w_gate", "w_up", "w_down", "top_k"]
    assert not hasattr(DroplessMoEMLP(16, 8, 4, top_k=2), "held")
    held = HeldMoEMLP(16, 8, 8, top_k=2, held=(2, 4), shared_width=8)
    assert held.w_gate.shape == [4, 16, 8] and held.gate.shape == [16, 8]
    assert held.select_bias.optimize_attr["learning_rate"] == 0.0
    x = paddle.to_tensor(np.random.default_rng(0).normal(
        size=(2, 8, 16)).astype(np.float32))
    assert held(x).shape == [2, 8, 16]
    stats = {k: np.asarray(v._value) for k, v in held.stats.items()}
    assert stats["moe/routed"] == 32 and stats["moe/rows"].shape == (4,)
    assert stats["moe/assigned"] == stats["moe/rows"].sum()


# --- the model ------------------------------------------------------------------
def weights_of(model):
    """(one dict a layer, the others) as the reference takes them."""
    names, tensors = state_tensors(model)[:2]
    layers = []
    for period in model.periods:
        for layer in period.layers:
            n, t = state_tensors(layer)[:2]
            layers.append({k: np.asarray(v._value) for k, v in zip(n, t)})
    other = {n: np.asarray(t._value) for n, t in zip(names, tensors)
             if not n.startswith("periods.")}
    return layers, other


def ref_cfg(c, **more):
    return dict(heads=c.num_attention_heads, kv_heads=c.num_key_value_heads,
                head_dim=c.head_dim, linear_heads=c.linear_attn_num_heads,
                linear_head_dim=c.linear_attn_head_dim,
                top_k=c.num_experts_per_tok, eps=c.rms_norm_eps, **more)


@pytest.fixture(scope="module")
def small():
    paddle.seed(3)
    model = SolarOpen2(SolarOpen2Config.tiny(experts_held=HELD))
    tokens = np.random.default_rng(0).integers(0, 512, (2, 96),
                                               dtype=np.int32)
    return model, tokens


def test_preset_carries_the_catalog_rows_widths():
    c = SolarOpen2Config.solar_open2_250b()
    assert (c.hidden_size, c.num_hidden_layers, c.vocab_size) == \
        (4096, 48, 196608)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == \
        (64, 8, 128)
    assert (c.linear_attn_num_heads, c.linear_attn_head_dim,
            c.short_conv_kernel_size, c.gqa_interval) == (64, 128, 4, 3)
    assert (c.n_routed_experts, c.num_experts_per_tok,
            c.moe_intermediate_size, c.n_shared_experts) == (320, 8, 1280, 1)
    # 250.3 B parameters by the equations, the model's name
    assert round(c.num_params() / 1e9, 1) == 250.3
    p = c.layer_params()
    assert [round(p[k] / 1e6, 1) for k in ("kda", "gqa", "dense",
                                           "expert")] == \
        [137.7, 109.1, 17.0, 15.7]
    with pytest.raises(ValueError, match="periods"):
        SolarOpen2Config(num_hidden_layers=6)


def test_num_params_counts_the_parameters_built(small):
    model, _ = small
    built = sum(int(np.prod(p.shape)) for p in model.parameters())
    assert model.config.num_params() == built
    cut = SolarOpen2Config(num_hidden_layers=4, vocab_size=24576,
                           experts_held=(0, 8))
    assert round(cut.num_params() / 1e9, 3) == 1.295


def test_logits_match_the_reference(small):
    model, tokens = small
    got = np.asarray(model(paddle.to_tensor(tokens))._value)
    layers, other = weights_of(model)
    want = np.asarray(jax.jit(lambda l, o: ref.forward(    # one program
        l, o, tokens, ref_cfg(model.config), held=HELD))(layers, other))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(np.abs(want).max()))


def test_the_references_attention_in_blocks_is_its_attention(small):
    model, tokens = small
    layers, other = weights_of(model)
    whole = ref.forward(layers, other, tokens, ref_cfg(model.config), HELD)
    blocks = ref.forward(layers, other, tokens,
                         ref_cfg(model.config, attention_rows=32), HELD)
    np.testing.assert_allclose(blocks, whole, rtol=0, atol=1e-5)


def test_parameter_gradients_match_the_reference(small):
    model, tokens = small
    layers, other = weights_of(model)
    cfg = ref_cfg(model.config)
    # (one program: the reference's gradient dispatched operation by
    # operation took most of this test's minute)
    want_layers, want_other = jax.jit(jax.grad(
        lambda l, o: ref.loss(l, o, tokens, cfg, HELD), argnums=(0, 1)))(
        [{k: jnp.asarray(v) for k, v in w.items()} for w in layers],
        {k: jnp.asarray(v) for k, v in other.items()})
    loss = model.loss(paddle.to_tensor(tokens))
    assert float(loss.numpy()) == pytest.approx(
        float(ref.loss(layers, other, tokens, cfg, HELD)), rel=1e-6)
    loss.backward()
    want = dict(want_other)
    for i, w in enumerate(want_layers):
        want.update({f"periods.{i // 4}.layers.{i % 4}.{k}": v
                     for k, v in w.items()})
    names = state_tensors(model)[0]
    assert set(names) == set(want)
    for name, p in zip(names, model.parameters()):
        scale = float(np.abs(want[name]).max())
        if name.endswith("select_bias"):
            assert scale == 0 and not np.asarray(p.grad._value).any()
            continue
        assert scale > 0, name
        # float32 on both sides: 2e-5 of the gradient's largest entry
        np.testing.assert_allclose(np.asarray(p.grad._value),
                                   np.asarray(want[name]), rtol=0,
                                   atol=2e-5 * scale, err_msg=name)


def trainer(model, optimizer, n_micro=2):
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh

    mesh = create_mesh({"dp": 1, "pp": 1, "tp": 1, "sp": 1},
                       jax.devices()[:1])
    return HybridPipelineTrainer(model, optimizer, DistributedStrategy(),
                                 mesh, n_micro=n_micro)


def test_the_trainers_step_is_the_references_loss_and_gradients():
    """Through ``HybridPipelineTrainer`` (pp 1, two micro-batches): the loss
    is the mean of the micro-batches' reference losses, and one step of
    plain SGD at rate 1 moves every weight by the mean of their reference
    gradients; the selection bias stays where it was."""
    paddle.seed(3)
    model = SolarOpen2(SolarOpen2Config.tiny(experts_held=HELD))
    tokens = np.random.default_rng(7).integers(0, 512, (4, 64),
                                               dtype=np.int32)
    layers, other = weights_of(model)
    cfg = ref_cfg(model.config)
    as_jnp = lambda tree: jax.tree_util.tree_map(jnp.asarray, tree)

    def two(l, o):
        return 0.5 * (ref.loss(l, o, tokens[:2], cfg, HELD)
                      + ref.loss(l, o, tokens[2:], cfg, HELD))

    want, (g_layers, g_other) = jax.jit(jax.value_and_grad(
        two, argnums=(0, 1)))(        # one program, not an op at a time
        as_jnp(layers), as_jnp(other))
    tr = trainer(model, paddle.optimizer.SGD(
        1.0, parameters=model.parameters()))
    assert "callback" not in tr.aot_lower(tokens).as_text()
    got = float(tr.step(tokens))
    assert got == pytest.approx(float(want), rel=2e-6)
    stats = jax.device_get(tr.aux_stats)
    assert stats["moe/routed"] == tokens.size * 4 * 4
    assert stats["moe/assigned"] == stats["moe/rows"].sum()
    assert stats["moe/rows"].shape == (HELD[1],)
    for i, g in enumerate(g_layers):
        for k, v in g.items():
            moved = layers[i][k] - np.asarray(
                tr.block_vals[f"layers.{i}.{k}"][0, 0])
            # what float32 can tell of a weight's move: its own spacing
            np.testing.assert_allclose(
                moved, v, rtol=0, atol=3e-5 * float(np.abs(v).max())
                + float(np.spacing(np.abs(layers[i][k]).max())),
                err_msg=f"layer {i} {k}")
    new_other = dict(zip(tr.other_names, tr.other_vals))
    for k, v in g_other.items():
        np.testing.assert_allclose(
            other[k] - np.asarray(new_other[k]), v, rtol=0,
            atol=3e-5 * float(np.abs(v).max())
            + float(np.spacing(np.abs(other[k]).max())), err_msg=k)


def test_adamw_leaves_the_selection_bias_alone():
    paddle.seed(5)
    model = SolarOpen2(SolarOpen2Config.tiny(experts_held=HELD))
    before = [np.asarray(l.mlp.select_bias._value).copy()
              for l in model.periods[0].layers]
    router = np.asarray(model.periods[0].layers[1].mlp.gate._value).copy()
    tr = trainer(model, paddle.optimizer.AdamW(
        1e-2, parameters=model.parameters()))
    tokens = np.random.default_rng(1).integers(0, 512, (2, 64),
                                               dtype=np.int32)
    for _ in range(2):
        tr.step(tokens)
    for i, b in enumerate(before):
        np.testing.assert_array_equal(
            b, np.asarray(tr.block_vals[f"layers.{i}.mlp.select_bias"][0, 0]))
    assert np.abs(router - np.asarray(
        tr.block_vals["layers.1.mlp.gate"][0, 0])).max() > 1e-3


def test_a_profiled_step_feeds_the_expert_gauges():
    profiler.reset()
    paddle.seed(5)
    model = SolarOpen2(SolarOpen2Config.tiny(experts_held=HELD))
    tr = trainer(model, paddle.optimizer.SGD(
        0.0, parameters=model.parameters()))
    tokens = np.random.default_rng(1).integers(0, 512, (2, 64),
                                               dtype=np.int32)
    profiler.enable()
    try:
        tr.step(tokens)
        seen = profiler.summary()["metrics"]
    finally:
        profiler.disable()
    assert seen["moe/dropped_tokens"]["value"] == 0
    assert 1.0 <= seen["moe/expert_load_max_over_mean"]["value"] < HELD[1]
    assert seen["kda/scan_calls{path=xla}"]["value"] >= 3
    assert not [k for k in seen if "path=pallas" in k]


def test_short_convolution_and_l2norm_against_hand_written_cases():
    x = jnp.arange(1.0, 7.0).reshape(1, 6, 1)
    w = jnp.asarray([[1000.0], [100.0], [10.0], [1.0]])
    # the last tap on the token itself, zeros before the start
    want = [1.0, 12.0, 123.0, 1234.0, 2345.0, 3456.0]
    np.testing.assert_allclose(prog.causal_conv(x, w)[0, :, 0], want)
    np.testing.assert_allclose(ref.short_conv(x, w)[0, :, 0], want)
    y = jnp.asarray([[[3.0, 4.0, 0.0, 0.0]]])
    sums = prog._head_sums(y * y, 2)
    np.testing.assert_allclose(sums, [[[25.0, 0.0]]])
    np.testing.assert_allclose(prog._over_heads(sums, 2),
                               [[[25.0, 25.0, 0.0, 0.0]]])
    np.testing.assert_allclose(ref.l2norm(y[..., :2]), [[[0.6, 0.8]]],
                               atol=1e-6)


# --- what the other models' programs were, they are -----------------------------
def small_olmoe_step_text():
    from tests.test_olmoe import _trainer, small_config

    paddle.seed(3)
    tr = _trainer(GPT(small_config()), 1, n_micro=2)
    return tr.aot_lower(jax.ShapeDtypeStruct((4, 32), np.int32)).as_text()


#: sha256 of the small OLMoE step's lowered text. PR 31 (which gave
#: ``dropless_moe`` its held range) computed it on its parent and on its own
#: tree in one environment: equal. PR 36 changed the program on purpose and
#: recomputed it: the head's scan makes each chunk's gradients from the tile
#: its loss was made from (``ops/fused_ce.py``), and between the two texts
#: the only ``dot_general``s that differ are the head's, five by the
#: vocabulary before (the tile twice, the one-hot's outer product, ``dW``,
#: ``dx``) and three after. A change to that program on purpose recomputes
#: it and says so.
OLMOE_STEP_SHA256 = \
    "7e29db9d420bcf8442baf98fa38aaf12bd31c585a7c102b7f0e912b337099a9b"


def test_the_small_olmoe_step_is_the_parents_program():
    text = small_olmoe_step_text()
    assert hashlib.sha256(text.encode()).hexdigest() == OLMOE_STEP_SHA256, \
        f"{len(text.splitlines())} lines"
