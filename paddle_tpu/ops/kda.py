"""Gated delta-rule linear attention with a decay a key channel (Kimi Delta
Attention, arXiv:2510.26692; ``fla.ops.kda``), chunked, forward and backward.

``kda_attention(q, k, v, g, beta) -> o``, q, k, g ``[b, s, heads, dk]``, v
``[b, s, heads, dv]``, beta ``[b, s, heads]``; a head keeps a state ``S`` in
``R^{dk x dv}``, zero before the first token::

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(dk)

``g <= 0`` is the log of the decay, ``beta`` in [0, 2]. Nothing here
normalises q or k: what turns a layer's three projections into this scan's
operands (short convolution, SiLU, q's and k's ``l2norm`` a head) lives in
``ops/kda_prep.py``, one Pallas pass each way (``kda_prep``,
``kda_prep_bwd``: names that start with neither ``kda_fwd`` nor ``kda_bwd``,
which a trace's reader takes for this file's kernels), called by
``models/solar_open2.kda_mix`` just before ``kda_attention_flat``. ``g``
comes here in float32, as the layer makes it. **The decay has
a floor**: ``g`` is taken as ``max(g, G_MIN)``, ``G_MIN = -9`` a token and
channel (a decay of 1.2e-4, under what a bf16 operand keeps of the state it
multiplies; fla's chunked kernel asks ``g >= -5`` of its caller for the same
reason, ``safe_gate``). At and above the floor the chunked form below is
exact for any sequence; below it the result is the recurrence's with ``g``
at the floor, and ``g`` takes no gradient there.

**The chunked form.** With ``G`` the running sum of ``g`` inside a chunk of
``C`` tokens and ``S0`` the state before it, the chunk's pseudo-values ``u``
(``S_t = Diag(exp g_t) S_{t-1} + k_t u_t^T``) solve the unit lower-triangular
system ``(I + Diag(beta) M) u = beta * (v - (k * exp G) S0)`` where ``M[t, i]
= sum_c k_tc k_ic exp(G_tc - G_ic)`` for ``i < t``; then ``o = ((q * exp G) S0
+ Aq u) / sqrt(dk)`` with ``Aq`` the same form over q and ``i <= t``, and
``S1 = Diag(exp G_C) S0 + (k * exp(G_C - G))^T u``. Three things keep it
exact and on the matrix unit:

* No ``exp(-G)`` is formed. A row block of 16 tokens (a sub-chunk) takes its
  decays relative to its middle row ``r`` (row 8 of the 16): the right
  factor ``exp(r - G_i)`` is at most 1 for every earlier sub-chunk; inside
  its own, the left factor ``exp(G_t - r)`` grows over at most 8 tokens
  before ``r`` and the right one over at most 7 after it, and every entry
  kept (``i <= t``) is their product ``exp(G_t - G_i) <= 1``. So the
  products are exact while 8 tokens' decay stays inside float32, which
  ``G_MIN`` sees to (``exp(72)``), whatever the sequence.
* The triangular system is solved by blocks: the four diagonal blocks of 16
  rows by forward substitution, all at once on the vector unit (15 rank-one
  updates), then merged pairwise with four small products. With bf16
  inputs each of those runs as three bf16 passes over split operands
  (``hi + lo``), so the inverse carries float32's worth of the operands
  before it is rounded once, as an operand, like the rest.
* The state and every accumulation are float32; operands of the products
  are of the inputs' type (bf16 in training, float32 under test).

**The backward pass** is one sweep. Differentiated, the scan's forward rule
runs the forward sweep under the name ``kda_fwd_states`` and leaves, beside
``o``, what the backward sweep would otherwise make again: the state before
every chunk (float32 ``[b, heads, s / C, dk, dv]``, a copy of the scratch
the sweep holds anyway) and every chunk's ``(I + M Diag beta)^-1`` (``[b,
heads, s / C, C, C]`` in the operands' type, which is how every product
takes it). ``kda_bwd_grads`` then walks the chunks backwards with the
state's cotangent in VMEM and recomputes nothing but a chunk's own parts
(decays, pair matrices, pseudo-values: ``_chunk_parts``); a chunk's
gradients are written out by hand below (``_chunk_bwd``), checked against
autodiff of the token-by-token recurrence in tests/test_solar_open2.py.
What it costs the caller: the residuals are the five inputs and those two
arrays, 537 MB + 134 MB a layer and sequence of 8,192 tokens with 64 heads
of 128 (a bf16 inverse's 64 columns lie in tiles of 128 lanes). Under a ``jax.checkpoint`` around the layer (models/solar_open2.py
has one) they are born in the recomputed forward sweep and die in the
backward sweep right after it; a caller that differentiates a deep stack of
these layers with no checkpoint holds them a layer until its backward
pass. Not differentiated, the forward sweep (``kda_fwd``) writes ``o`` alone.

**Two paths, one body.** ``_chunk_fwd`` and ``_chunk_bwd`` are functions of
one head's one chunk. On one TPU device, with a sequence that is a multiple
of the chunk and heads of 128, they are the bodies of the Pallas kernels
``kda_fwd`` / ``kda_fwd_states`` and ``kda_bwd_grads``; everywhere else (the
CPU, a multi-device auto mesh, sizes that do not tile) ``lax.scan`` over
the chunks calls the same functions under ``vmap``, and its forward rule
stacks the carry it has. The path is observed
(``kernel_path``), never chosen: there is no argument, field or variable
for it. It is counted at trace time in ``kda/scan_calls{path=}``
(the profiler's registry: a count on the host, nothing in the program).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

__all__ = ["kda_attention", "kda_attention_flat", "kernel_path",
           "pallas_kda", "kda_recurrent", "CHUNK", "G_MIN"]

CHUNK = 64          # tokens a chunk
_SUB = 16           # tokens a sub-chunk
_REF = _SUB // 2    # the row of a sub-chunk its decays are relative to
G_MIN = -9.0        # the floor of g a token: exp(-_REF * G_MIN) is finite
_HEADS_A_STEP = 2   # heads one grid step works on (independent chains)
_VMEM_LIMIT = 64 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_DEFAULT = jax.lax.Precision.DEFAULT


def _mm(a, b, dims, dt):
    """A product with operands of type ``dt`` and float32 accumulation."""
    return jax.lax.dot_general(
        a.astype(dt), b.astype(dt), dims, preferred_element_type=_F32,
        precision=_HIGHEST if dt == _F32 else _DEFAULT)


def _mm_exact(a, b, dims, dt):
    """A product of float32 matrices that keeps float32's worth of both:
    one float32 product under test, three bf16 passes over operands split
    into ``hi + lo`` in training (the ``lo x lo`` pass is below 2^-16)."""
    if dt == _F32:
        return _mm(a, b, dims, _F32)
    a_hi, b_hi = a.astype(dt), b.astype(dt)
    a_lo = a - a_hi.astype(_F32)
    b_lo = b - b_hi.astype(_F32)
    return (_mm(a_hi, b_hi, dims, dt) + _mm(a_hi, b_lo, dims, dt)
            + _mm(a_lo, b_hi, dims, dt))


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _to_col(row):
    """[1, n] -> [n, 1] without a transpose: the diagonal, summed."""
    n = row.shape[1]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(col):
    n = col.shape[0]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _unit_lower_inverse(n_mat, dt):
    """(I + N)^-1 for a strictly lower-triangular N [C, C], by blocks. The
    diagonal blocks of ``_SUB`` rows are inverted by forward substitution,
    all at once: step ``i`` takes column ``i`` of every block times row
    ``i`` of its inverse so far from the rows below, 15 rank-one updates on
    the vector unit. Then blocks are merged pairwise, ``[[A, 0], [C,
    D]]^-1 = [[A^-1, 0], [-D^-1 C A^-1, D^-1]]``: two products a level,
    two levels. (The closed product ``(I - N)(I + N^2)(I + N^4)...`` is
    not used: with neighbouring keys alike and ``beta`` near 2 the entries
    of ``N`` are near 2, its powers reach 1e10 before they cancel, and
    float32 loses everything; seen on the chip, PERF.md section 6.)"""
    c = n_mat.shape[0]
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    blocks = c // _SUB
    diag = jnp.where(row // _SUB == col // _SUB, n_mat, 0.0)
    inv = (row == col).astype(_F32)
    for i in range(_SUB - 1):
        below = jnp.sum(jnp.where(col % _SUB == i, diag, 0.0), axis=1,
                        keepdims=True)                      # [C, 1]
        mine = jnp.where(row % _SUB == i, inv, 0.0).reshape(blocks, _SUB, c)
        mine = jnp.broadcast_to(jnp.sum(mine, axis=1, keepdims=True),
                                (blocks, _SUB, c)).reshape(c, c)
        inv = inv - below * mine
    size = _SUB
    while size < c:
        off = jnp.where((row // (2 * size) == col // (2 * size))
                        & (row // size != col // size), n_mat, 0.0)
        inv = inv - _mm_exact(_mm_exact(inv, off, _NN, dt), inv, _NN, dt)
        size *= 2
    return inv


def _decay_parts(g):
    """The chunk's decays from ``g`` [C, dk] float32: the running sum ``G``
    and every factor the chunk uses, none above ``exp`` of half a
    sub-chunk's decay. Returns a dict."""
    c = g.shape[0]
    tri = (_iota((c, c), 0) >= _iota((c, c), 1)).astype(_F32)
    big_g = _mm(tri, g, _NN, _F32)                      # inclusive sums
    total = jnp.sum(g, axis=0, keepdims=True)           # [1, dk] = G_C
    refs = [big_g[a + _REF:a + _REF + 1] for a in range(0, c, _SUB)]
    e_in = jnp.concatenate(
        [jnp.exp(big_g[a:a + _SUB] - r)
         for a, r in zip(range(0, c, _SUB), refs)])     # exp(G_t - r(t))
    gam = jnp.exp(big_g)                                # exp(G_t)
    # right factors of row block a: exp(r_a - G_i) for the rows up to its end
    rights = [jnp.exp(r - big_g[:a + _SUB])
              for a, r in zip(range(0, c, _SUB), refs)]
    return {"tri": tri, "e_in": e_in, "gam": gam, "rights": rights,
            "to_end": jnp.exp(total - big_g),           # exp(G_C - G_t)
            "end": jnp.exp(total)}                      # [1, dk]


def _padded(rows, c):
    """``rows`` [n, d] with zero rows up to ``c``."""
    n = rows.shape[0]
    if n == c:
        return rows
    return jnp.concatenate(
        [rows, jnp.zeros((c - n, rows.shape[1]), rows.dtype)])


def _pair_matrices(qf, kf, dec, dt):
    """``Aq`` (q against the keys up to and with the token) and ``M`` (k
    against the keys before it), [C, C] each, and the row blocks' operands
    for the backward pass."""
    c = qf.shape[0]
    low = _iota((c, c), 0) >= _iota((c, c), 1)
    strict = _iota((c, c), 0) > _iota((c, c), 1)
    q_rows, k_rows, blocks = [], [], []
    for n, a in enumerate(range(0, c, _SUB)):
        e = dec["e_in"][a:a + _SUB]
        left = jnp.concatenate([qf[a:a + _SUB] * e, kf[a:a + _SUB] * e])
        right = _padded(kf[:a + _SUB] * dec["rights"][n], c)
        blk = _mm(left, right, _NT, dt)                 # [2 sub, C]
        q_rows.append(blk[:_SUB])
        k_rows.append(blk[_SUB:])
        blocks.append((left, right))
    return (jnp.where(low, jnp.concatenate(q_rows), 0.0),
            jnp.where(strict, jnp.concatenate(k_rows), 0.0), blocks)


def _chunk_parts(q, k, v, g, beta_row, s0, inv=None):
    """Everything one chunk's forward computes, for both passes; the
    backward pass hands in the ``inv`` the forward pass left."""
    dt = q.dtype
    qf, kf, vf = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    dec = _decay_parts(g)
    aq, m, blocks = _pair_matrices(qf, kf, dec, dt)
    if inv is None:                                     # (I + M Diag b)^-1
        inv = _unit_lower_inverse(m * beta_row, dt).astype(dt)
    beta_col = _to_col(beta_row)
    kg, qg = kf * dec["gam"], qf * dec["gam"]
    r = vf - _mm(kg, s0, _NN, dt)
    pr = _mm(inv, r, _NN, dt)
    u = beta_col * pr
    return {"dt": dt, "qf": qf, "kf": kf, "dec": dec, "aq": aq, "m": m,
            "blocks": blocks, "inv": inv, "beta_col": beta_col, "kg": kg,
            "qg": qg, "pr": pr, "u": u, "k_end": kf * dec["to_end"]}


def _chunk_fwd(q, k, v, g, beta_row, s0, scale):
    """One head's one chunk: q, k [C, dk], v [C, dv], g [C, dk] float32,
    beta_row [1, C] float32, s0 [dk, dv] float32 -> (o [C, dv] float32,
    s1, and the chunk's ``(I + M Diag beta)^-1`` [C, C] as the products
    take it, in q's type)."""
    p = _chunk_parts(q, k, v, g, beta_row, s0)
    o = scale * (_mm(p["qg"], s0, _NN, p["dt"])
                 + _mm(p["aq"], p["u"], _NN, p["dt"]))
    s1 = _to_col(p["dec"]["end"]) * s0 + _mm(p["k_end"], p["u"], _TN, p["dt"])
    return o, s1, p["inv"]


def _chunk_bwd(q, k, v, g, beta_row, s0, inv, do, ds1, scale):
    """The chunk's gradients from what the forward pass left (the state
    ``s0`` before the chunk and ``inv``), ``do`` [C, dv] and the cotangent
    ``ds1`` of the state after it: (dq, dk, dv, dg, dbeta_row, ds0),
    float32."""
    p = _chunk_parts(q, k, v, g, beta_row, s0, inv)
    dt, qf, kf, dec = p["dt"], p["qf"], p["kf"], p["dec"]
    c = qf.shape[0]
    low = _iota((c, c), 0) >= _iota((c, c), 1)
    strict = _iota((c, c), 0) > _iota((c, c), 1)
    dop = scale * do.astype(_F32)
    end_col = _to_col(dec["end"])

    # o = scale (qg S0 + Aq u);  S1 = Diag(end) S0 + k_end^T u
    du = _mm(p["aq"], dop, _TN, dt) + _mm(p["k_end"], ds1, _NN, dt)
    daq = jnp.where(low, _mm(dop, p["u"], _NT, dt), 0.0)
    dqg = _mm(dop, s0, _NT, dt)
    ds0 = _mm(p["qg"], dop, _TN, dt) + end_col * ds1
    dk_end = _mm(p["u"], ds1, _NT, dt)
    d_end = _to_row(end_col * jnp.sum(s0 * ds1, axis=1, keepdims=True))

    # u = beta_col * pr, pr = inv r, inv = (I + M Diag beta)^-1
    dbeta_col = jnp.sum(du * p["pr"], axis=1, keepdims=True)
    w = _mm(p["inv"], p["beta_col"] * du, _TN, dt)      # the cotangent of r
    dn = -jnp.where(strict, _mm(w, p["pr"], _NT, dt), 0.0)
    dm = dn * beta_row
    dbeta_row = jnp.sum(dn * p["m"], axis=0, keepdims=True) \
        + _to_row(dbeta_col)
    # r = v - kg S0
    dv = w
    dkg = -_mm(w, s0, _NT, dt)
    ds0 = ds0 - _mm(p["kg"], w, _TN, dt)

    # the products with one decay a row
    dq = dqg * dec["gam"]
    dk = dkg * dec["gam"] + dk_end * dec["to_end"]
    t_end = dk_end * p["k_end"]
    dbig = dqg * p["qg"] + dkg * p["kg"] - t_end        # cotangent of G
    d_total = d_end + jnp.sum(t_end, axis=0, keepdims=True)

    # Aq and M, row block by row block
    dq_rows, dk_rows, dg_rows = [], [], []
    dk_right = jnp.zeros_like(kf)
    dg_right = jnp.zeros_like(kf)
    rowid = _iota((c, 1), 0)
    for n, a in enumerate(range(0, c, _SUB)):
        left, right = p["blocks"][n]
        dblk = jnp.concatenate([daq[a:a + _SUB], dm[a:a + _SUB]])
        dleft = _mm(dblk, right, _NN, dt)               # [2 sub, dk]
        dright = _mm(dblk, left, _TN, dt)               # [C, dk]
        e = dec["e_in"][a:a + _SUB]
        dq_rows.append(dleft[:_SUB] * e)
        dk_rows.append(dleft[_SUB:] * e)
        t_left = dleft[:_SUB] * left[:_SUB] + dleft[_SUB:] * left[_SUB:]
        dg_rows.append(t_left)
        t_right = dright * right
        dk_right = dk_right + dright * _padded(dec["rights"][n], c)
        # the reference row r_a = G[a + _REF] takes what both sides give it
        d_ref = jnp.sum(t_right, axis=0, keepdims=True) \
            - jnp.sum(t_left, axis=0, keepdims=True)
        dg_right = dg_right - t_right \
            + jnp.where(rowid == a + _REF, d_ref, 0.0)
    dq = dq + jnp.concatenate(dq_rows)
    dk = dk + jnp.concatenate(dk_rows) + dk_right
    dbig = dbig + jnp.concatenate(dg_rows) + dg_right
    # G = tri g, G_C = sum g
    dg = _mm(dec["tri"], dbig, _TN, _F32) + d_total
    return dq, dk, dv, dg, dbeta_row, ds0


# ---------------------------------------------------------------------------
# the token-by-token recurrence: the definition, and the tests' reference
# ---------------------------------------------------------------------------
def kda_recurrent(q, k, v, g, beta, scale=None):
    """The recurrence itself, one token a step, float32: what the chunked
    paths must equal. Shapes as ``kda_attention``."""
    b, s, h, dk = q.shape
    scale = dk ** -0.5 if scale is None else scale
    f = lambda a: jnp.moveaxis(a.astype(_F32), 1, 0)
    hi = functools.partial(jnp.einsum, precision=_HIGHEST)

    def step(state, x):
        qt, kt, vt, gt, bt = x                  # [b, h, d] ...; bt [b, h]
        state = state * jnp.exp(gt)[..., None]
        err = vt - hi("bhk,bhkv->bhv", kt, state)
        state = state + hi("bhk,bhv->bhkv", kt * bt[..., None], err)
        return state, hi("bhk,bhkv->bhv", qt, state) * scale

    s0 = jnp.zeros((b, h, dk, v.shape[-1]), _F32)
    _, o = jax.lax.scan(step, s0, (f(q), f(k), f(v), f(g), f(beta)))
    return jnp.moveaxis(o, 0, 1)


# ---------------------------------------------------------------------------
# the XLA path: scan over chunks, vmap over batch and heads
# ---------------------------------------------------------------------------
def _chunked(a, c):
    """[b, s, h, d] -> [n_chunks, b, h, c, d]."""
    b, s, h, d = a.shape
    return jnp.transpose(a.reshape(b, s // c, c, h, d), (1, 0, 3, 2, 4))


def _unchunked(a):
    n, b, h, c, d = a.shape
    return jnp.transpose(a, (1, 0, 3, 2, 4)).reshape(b, n * c, h, d)


def _beta_rows(beta, c):
    """[b, s, h] -> [n_chunks, b, h, 1, c]."""
    b, s, h = beta.shape
    return jnp.transpose(beta.reshape(b, s // c, c, h),
                         (1, 0, 3, 2))[:, :, :, None, :]


def _over_heads(fn):
    return jax.vmap(jax.vmap(fn))


def _xla_fwd(q, k, v, g, beta, scale):
    """-> (o, and what the backward pass reads: the state before every
    chunk and every chunk's inverse, [n_chunks, b, h, ...], the scan's
    carry and an output it has anyway)."""
    c = CHUNK
    b, h, dk, dv = q.shape[0], q.shape[2], q.shape[3], v.shape[3]
    xs = (_chunked(q, c), _chunked(k, c), _chunked(v, c), _chunked(g, c),
          _beta_rows(beta, c))

    def step(s0, x):
        o, s1, inv = _over_heads(
            functools.partial(_chunk_fwd, scale=scale))(*x, s0)
        return s1, (o, s0, inv)

    _, (o, states, invs) = jax.lax.scan(
        step, jnp.zeros((b, h, dk, dv), _F32), xs)
    return _unchunked(o).astype(v.dtype), states, invs


def _xla_bwd(q, k, v, g, beta, states, invs, do, scale):
    c = CHUNK
    xs = (_chunked(q, c), _chunked(k, c), _chunked(v, c), _chunked(g, c),
          _beta_rows(beta, c), states, invs, _chunked(do, c))

    def step(ds1, x):
        dq, dk, dv, dg, db, ds0 = _over_heads(
            functools.partial(_chunk_bwd, scale=scale))(*x, ds1)
        return ds0, (dq, dk, dv, dg, db)

    _, (dq, dk, dv, dg, db) = jax.lax.scan(
        step, jnp.zeros_like(states[0]), xs, reverse=True)
    dbeta = jnp.transpose(db[:, :, :, 0, :], (1, 0, 3, 2)).reshape(
        beta.shape)
    return (_unchunked(dq).astype(q.dtype), _unchunked(dk).astype(k.dtype),
            _unchunked(dv).astype(v.dtype), _unchunked(dg),
            dbeta.astype(beta.dtype))


# ---------------------------------------------------------------------------
# the Pallas path. Arrays stay [b, s, heads * d]: a block is one chunk of a
# few heads' columns, so nothing is transposed on the way in or out. beta
# goes in as [b, heads, n_chunks, C] and a (batch, heads) block of it stays
# in VMEM for the whole sequence.
# ---------------------------------------------------------------------------
def _head_cols(ref, h, d):
    return ref[0, :, h * d:(h + 1) * d]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *more, hb, dk, dv,
                scale):
    """``more``: the blocks the forward rule leaves the backward pass (the
    state before the chunk, the chunk's inverse), if it is the forward
    rule that runs, then the state's scratch."""
    *left, s_ref = more
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    for h in range(hb):
        s0 = s_ref[h]
        o, s1, inv = _chunk_fwd(
            _head_cols(q_ref, h, dk), _head_cols(k_ref, h, dk),
            _head_cols(v_ref, h, dv), _head_cols(g_ref, h, dk),
            b_ref[0, h, pl.ds(c, 1), :], s0, scale)
        o_ref[0, :, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)
        s_ref[h] = s1
        for ref, part in zip(left, (s0, inv)):
            ref[0, h, 0] = part


def _grads_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, inv_ref, do_ref,
                  dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *, hb,
                  dk, dv, scale, n_chunks):
    step = pl.program_id(2)
    c = n_chunks - 1 - step                 # the chunks, last first

    @pl.when(step == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    for h in range(hb):
        dq, dkk, dvv, dg, db, ds0 = _chunk_bwd(
            _head_cols(q_ref, h, dk), _head_cols(k_ref, h, dk),
            _head_cols(v_ref, h, dv), _head_cols(g_ref, h, dk),
            b_ref[0, h, pl.ds(c, 1), :], st_ref[0, h, 0], inv_ref[0, h, 0],
            _head_cols(do_ref, h, dv), ds_ref[h], scale)
        dq_ref[0, :, h * dk:(h + 1) * dk] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, h * dk:(h + 1) * dk] = dkk.astype(dk_ref.dtype)
        dv_ref[0, :, h * dv:(h + 1) * dv] = dvv.astype(dv_ref.dtype)
        dg_ref[0, :, h * dk:(h + 1) * dk] = dg
        db_ref[0, h, pl.ds(c, 1), :] = db
        ds_ref[h] = ds0


def _heads_a_step(heads: int) -> int:
    return _HEADS_A_STEP if heads % _HEADS_A_STEP == 0 else 1


def _specs(s, dk, dv, hb, order):
    """The block specs the kernels share; ``order(c)`` is the chunk a grid
    step works on. ``cols(d)``: a chunk of ``hb`` heads' columns; ``beta``;
    ``left``: what the forward rule leaves, a chunk's state and inverse."""
    nc = s // CHUNK
    cols = lambda d: pl.BlockSpec(
        (1, CHUNK, hb * d), lambda i, j, c: (i, order(c), j))
    beta = pl.BlockSpec((1, hb, nc, CHUNK), lambda i, j, c: (i, j, 0, 0))
    left = [pl.BlockSpec((1, hb, 1) + tile,
                         lambda i, j, c: (i, j, order(c), 0, 0))
            for tile in ((dk, dv), (CHUNK, CHUNK))]
    return cols, beta, left


def _beta_blocks(beta):
    b, s, h = beta.shape
    return jnp.transpose(beta.astype(_F32).reshape(b, s // CHUNK, CHUNK, h),
                         (0, 3, 1, 2))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _pallas_fwd(q, k, v, g, beta, scale, leave):
    """q, k, g [b, s, heads * dk], v [b, s, heads * dv], beta [b, s,
    heads] -> o [b, s, heads * dv]; with ``leave`` (the forward rule)
    -> (o, states float32 [b, heads, n_chunks, dk, dv], inverses [b,
    heads, n_chunks, C, C] in q's type), from the same sweep."""
    b, s, h = beta.shape
    dk, dv = q.shape[2] // h, v.shape[2] // h
    hb, nc = _heads_a_step(h), s // CHUNK
    cols, beta_spec, left = _specs(s, dk, dv, hb, lambda c: c)
    outs = [(cols(dv), jax.ShapeDtypeStruct(v.shape, v.dtype)),
            (left[0], jax.ShapeDtypeStruct((b, h, nc, dk, dv), _F32)),
            (left[1], jax.ShapeDtypeStruct((b, h, nc, CHUNK, CHUNK),
                                           q.dtype))][:3 if leave else 1]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, dk=dk, dv=dv, scale=scale),
        name="kda_fwd_states" if leave else "kda_fwd",
        grid=(b, h // hb, nc),
        in_specs=[cols(dk), cols(dk), cols(dv), cols(dk), beta_spec],
        out_specs=[spec for spec, _ in outs],
        out_shape=[shape for _, shape in outs],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=_params(),
        interpret=_interpret(),
    )(q, k, v, g, _beta_blocks(beta))
    return tuple(out) if leave else out[0]


def _pallas_bwd(q, k, v, g, beta, states, invs, do, scale):
    b, s, h = beta.shape
    dk, dv = q.shape[2] // h, v.shape[2] // h
    hb, nc = _heads_a_step(h), s // CHUNK
    cols, beta_spec, left = _specs(s, dk, dv, hb, lambda c: nc - 1 - c)
    dq, dkk, dvv, dg, db = pl.pallas_call(
        functools.partial(_grads_kernel, hb=hb, dk=dk, dv=dv, scale=scale,
                          n_chunks=nc),
        name="kda_bwd_grads",
        grid=(b, h // hb, nc),
        in_specs=[cols(dk), cols(dk), cols(dv), cols(dk), beta_spec]
        + left + [cols(dv)],
        out_specs=[cols(dk), cols(dk), cols(dv), cols(dk), beta_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, _F32),
            jax.ShapeDtypeStruct((b, h, nc, CHUNK), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=_params(),
        interpret=_interpret(),
    )(q, k, v, g, _beta_blocks(beta), states, invs, do.astype(v.dtype))
    return dq, dkk, dvv, dg, jnp.transpose(db, (0, 2, 3, 1)).reshape(b, s, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def pallas_kda(q, k, v, g, beta, scale):
    """The kernels themselves, whatever the platform (interpreted on the
    CPU), on ``[b, s, heads * d]`` arrays: what tests/test_solar_open2.py
    compares with the recurrence."""
    return _pallas_fwd(q, k, v, g, beta, scale, leave=False)


def _pallas_kda_fwd(q, k, v, g, beta, scale):
    o, states, invs = _pallas_fwd(q, k, v, g, beta, scale, leave=True)
    return o, (q, k, v, g, beta, states, invs)


pallas_kda.defvjp(_pallas_kda_fwd,
                  lambda scale, res, do: _pallas_bwd(*res, do, scale))


# ---------------------------------------------------------------------------
# the entry
# ---------------------------------------------------------------------------
def kernel_path(seq: int, dk: int, dv: int) -> str:
    """``"pallas"`` or ``"xla"`` for a scan of these sizes traced here: the
    kernels need the TPU as the target, no multi-device auto mesh open at
    the trace, whole chunks and heads that fill the lanes."""
    from ..core.place import target_platform
    from ..distributed import context as dctx

    if (target_platform() == "tpu" and dctx.kernel_auto_axes() is None
            and seq % CHUNK == 0 and dk % 128 == 0 and dv % 128 == 0):
        return "pallas"
    return "xla"


def _heads_apart(a, heads):
    b, s, hd = a.shape
    return a.reshape(b, s, heads, hd // heads)


def _xla_kda_fwd(q, k, v, g, beta, scale):
    """The ``lax.scan`` path's forward rule, on the kernels' layout."""
    heads = beta.shape[2]
    o, states, invs = _xla_fwd(
        *(_heads_apart(a, heads) for a in (q, k, v, g)), beta, scale)
    return o.reshape(v.shape), (q, k, v, g, beta, states, invs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _xla_kda(q, k, v, g, beta, scale):
    return _xla_kda_fwd(q, k, v, g, beta, scale)[0]


def _xla_kda_bwd(scale, res, do):
    *ins, beta, states, invs = res
    heads = beta.shape[2]
    grads = _xla_bwd(*(_heads_apart(a, heads) for a in ins), beta, states,
                     invs, _heads_apart(do, heads), scale)
    return tuple(d.reshape(a.shape) for d, a in zip(grads, res[:5]))


_xla_kda.defvjp(_xla_kda_fwd, _xla_kda_bwd)


def kda_attention_flat(q, k, v, g, beta, scale=None):
    """``kda_attention`` on arrays whose heads lie side by side in the last
    axis: q, k, g [b, s, heads * dk], v [b, s, heads * dv], beta [b, s,
    heads] -> o [b, s, heads * dv]. What a layer calls: its projections
    give this layout and the kernels read it as it is."""
    from ..profiler import metrics

    s, heads = beta.shape[1], beta.shape[2]
    dk, dv = q.shape[2] // heads, v.shape[2] // heads
    scale = float(dk ** -0.5 if scale is None else scale)
    g, beta = jnp.maximum(g.astype(_F32), G_MIN), beta.astype(_F32)
    path = kernel_path(s, dk, dv)
    metrics.registry().counter("kda/scan_calls{path=%s}" % path).add(1)
    if path == "pallas":
        return pallas_kda(q, k, v, g, beta, scale)
    pad = -s % CHUNK
    if pad:
        # zero keys, no decay, beta 0: the state passes through unchanged
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                            for a in (q, k, v, g, beta))
    return _xla_kda(q, k, v, g, beta, scale)[:, :s]


def kda_attention(q, k, v, g, beta, scale=None):
    """q, k, g [b, s, heads, dk], v [b, s, heads, dv], beta [b, s, heads] ->
    o [b, s, heads, dv] in ``v``'s type; the state starts at 0.
    Differentiable towards all five. See the module's text."""
    b, s = q.shape[:2]
    flat = lambda a: a.reshape(b, s, -1)
    return kda_attention_flat(flat(q), flat(k), flat(v), flat(g), beta,
                              scale).reshape(v.shape)
