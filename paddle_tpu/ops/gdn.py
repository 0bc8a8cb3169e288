"""The gated delta rule **served** (Gated DeltaNet, arXiv:2412.06464;
``fla.layers.GatedDeltaNet``): a head keeps a state ``S`` in ``R^{dk x dv}``
that lives in a slot of a pool between ticks::

    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(dk)

``g <= 0`` a head (a scalar: ``ops/kda.py``'s recurrence with the decay equal
over a head's key channels, which commutes with the reflection), ``beta`` in
[0, 2]. ``ops/kda.py`` is the rule trained: every sequence starts at zero and
only ``o`` leaves. Here rows enter with their slot's state and leave it
behind:

``gdn_step_rows``   the decode rows, one token against a state (read it,
                    write it: bound by HBM)
``gdn_chunk_rows``  the chunk rows, ``w`` tokens from the slot's state
                    (``ops/kda._chunk_fwd``, the body the trained scan runs,
                    with the gate broadcast); positions at and past a row's
                    length are the identity (``beta = 0, g = 0``)
``conv_step``,      the depthwise causal convolution before them, with the
``conv_rows``       ``taps - 1`` positions it looks back on carried in

**The state's layout** is this file's, held by ``serving.paged_cache.
StatePools``: ``[layers, slots + 1, heads / 2, dk, 2 dv]`` float32, two
heads' values side by side on the lanes (``pack_state``). 192 is not a
multiple of a tile's 128 lanes and 384 is: a head alone would be stored and
moved at 256. Slot 0 is the null slot, as page 0 is the null page: a dead
row (``slots`` 0: an empty slot, one still prefilling) reads and writes it
and no tenant's state is touched. The stack is updated in place.

**Two spellings, picked where the program is traced** (``gdn_path``: the
TPU as the target, no auto mesh, an even number of heads, pairs that fill
the lanes), never by an argument, and counted there in
``gdn/step_calls{path=}`` and ``gdn/chunk_calls{path=}``. The ``jax.numpy``
spelling (``xla_step``, ``xla_chunk``) is the kernels' reference and the
path off the chip. The kernels (``gdn_step``, ``gdn_chunk``) reach a row's
slot by a scalar-prefetched index, so nothing gathers or scatters a state
outside them. ``gdn_chunk`` pads the keys to 128 channels (exact: a zero
channel adds nothing) and runs a pair's two heads over the pair's whole
lanes with the other head's masked to zero, so every slice it takes is a
tile's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kda
from .flash_attention import _interpret

__all__ = ["gdn_step_rows", "gdn_chunk_rows", "conv_step", "conv_rows",
           "gdn_path",
           "pack_state", "unpack_state", "xla_step", "xla_chunk",
           "pallas_step", "pallas_chunk", "gdn_recurrent"]

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
_VMEM_LIMIT = 96 * 1024 * 1024


def pack_state(s):
    """``[..., heads, dk, dv]`` -> ``[..., heads / 2, dk, 2 dv]``: heads
    ``2p`` and ``2p + 1`` side by side (an odd count: each head alone)."""
    *lead, h, dk, dv = s.shape
    if h % 2:
        return s
    s = s.reshape(*lead, h // 2, 2, dk, dv)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, h // 2, dk, 2 * dv)


def unpack_state(s, heads: int):
    """``pack_state``'s inverse for ``heads`` heads."""
    *lead, hp, dk, dv2 = s.shape
    if hp == heads:
        return s
    s = s.reshape(*lead, hp, dk, 2, dv2 // 2)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, heads, dk, dv2 // 2)


def gdn_path(heads: int, dk: int, dv: int) -> str:
    """``"pallas"`` or ``"xla"`` for heads of these sizes traced here."""
    from ..core.place import target_platform
    from ..distributed import context as dctx

    if (target_platform() == "tpu" and dctx.kernel_auto_axes() is None
            and heads % 2 == 0 and dk % 8 == 0 and dk <= _LANES
            and (2 * dv) % _LANES == 0):
        return "pallas"
    return "xla"


def _count(name: str, path: str) -> None:
    from ..profiler import metrics

    metrics.registry().counter("gdn/%s{path=%s}" % (name, path)).add(1)


# ---------------------------------------------------------------------------
# the jax.numpy spelling: pure functions of the rows' own states
# ---------------------------------------------------------------------------
def xla_step(q, k, v, g, beta, s):
    """One token a row: q, k ``[n, H, dk]``, v ``[n, H, dv]``, g, beta ``[n,
    H]``, s ``[n, H, dk, dv]`` float32 -> ``(o [n, H, dv] float32, s)``."""
    hi = functools.partial(jnp.einsum, precision=_HIGHEST)
    qf, kf, vf = (a.astype(_F32) for a in (q, k, v))
    dec = jnp.exp(g.astype(_F32))
    ks = hi("nhk,nhkv->nhv", kf, s)
    w = beta.astype(_F32)[..., None] * (vf - dec[..., None] * ks)
    s = dec[..., None, None] * s + kf[..., :, None] * w[..., None, :]
    return hi("nhk,nhkv->nhv", qf, s) * q.shape[-1] ** -0.5, s


def gdn_recurrent(q, k, v, g, beta, s0):
    """The recurrence itself over ``[n, t, H, ...]`` from ``s0``, a token a
    step, float32: what the chunked paths must equal. -> ``(o, s1)``."""
    f = lambda a: jnp.moveaxis(a, 1, 0)                     # noqa: E731

    def step(s, x):
        o, s = xla_step(*x, s)
        return s, o

    s1, o = jax.lax.scan(step, s0.astype(_F32),
                         (f(q), f(k), f(v), f(g), f(beta)))
    return jnp.moveaxis(o, 0, 1), s1


def _masked(g, beta, row_len):
    """``g`` and ``beta`` ``[n, w, H]`` float32 with the positions at and
    past each row's length made the identity, ``g`` at ``kda.G_MIN`` or
    above (the chunked form's floor)."""
    w = g.shape[1]
    keep = (jnp.arange(w, dtype=jnp.int32)[None, :] < row_len[:, None])[
        ..., None]
    return (jnp.where(keep, jnp.maximum(g.astype(_F32), kda.G_MIN), 0.0),
            jnp.where(keep, beta.astype(_F32), 0.0))


def xla_chunk(q, k, v, g, beta, s0, row_len):
    """``w`` tokens a row from ``s0``: q, k ``[n, w, H, dk]``, v ``[n, w, H,
    dv]``, g, beta ``[n, w, H]``, s0 ``[n, H, dk, dv]`` float32, row_len
    ``[n]`` -> ``(o [n, w, H, dv] float32, s1)``. A ``lax.scan`` over chunks
    of ``kda.CHUNK`` tokens of ``kda._chunk_fwd`` under ``vmap``."""
    n, w, h, dk = q.shape
    c = kda.CHUNK
    g, beta = _masked(g, beta, row_len)
    pad = -w % c
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    gk = jnp.broadcast_to(g[..., None], g.shape + (dk,))
    xs = (kda._chunked(q, c), kda._chunked(k, c), kda._chunked(v, c),
          kda._chunked(gk, c), kda._beta_rows(beta, c))
    body = kda._over_heads(functools.partial(kda._chunk_fwd,
                                             scale=dk ** -0.5))

    def step(s, x):
        o, s1, _ = body(*x, s)
        return s1, o

    s1, o = jax.lax.scan(step, s0.astype(_F32), xs)
    return kda._unchunked(o)[:, :w], s1


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _step_kernel(slots_ref, layer_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                 s_ref, o_ref, s_out, *, dv: int):
    """Grid (row,): every pair of the row's heads, one after the other.
    ``S' = e^g S + k (beta (v - e^g k^T S))^T`` and ``o = S'^T q``, all on
    the vector unit in float32: a pair's block is ``[dk, 2 dv]``, a head's
    keys are a column over its half of the lanes."""
    del slots_ref, layer_ref
    hp, dk, dv2 = s_ref.shape[2:]
    left = jax.lax.broadcasted_iota(jnp.int32, (1, dv2), 1) < dv
    qf, kf = q_ref[0].astype(_F32), k_ref[0].astype(_F32)   # [H, dk]
    scale = dk ** -0.5
    for p in range(hp):
        a, b = 2 * p, 2 * p + 1
        col = lambda x, h: kda._to_col(x[h:h + 1])          # noqa: E731
        kmat = jnp.where(left, col(kf, a), col(kf, b))      # [dk, 2 dv]
        qmat = jnp.where(left, col(qf, a), col(qf, b))
        pair = lambda ref: jnp.where(                       # noqa: E731
            left, ref[0, a:a + 1, :].astype(_F32),
            ref[0, b:b + 1, :].astype(_F32))                # [1, 2 dv]
        dec, beta = jnp.exp(pair(g_ref)), pair(b_ref)
        vrow = v_ref[0, p:p + 1, :].astype(_F32)
        s = s_ref[0, 0, p]
        ks = jnp.sum(kmat * s, axis=0, keepdims=True)
        s = dec * s + kmat * (beta * (vrow - dec * ks))
        s_out[0, 0, p] = s
        o_ref[0, p:p + 1, :] = scale * jnp.sum(qmat * s, axis=0,
                                               keepdims=True)


def pallas_step(q, k, v, g, beta, state, layer, slots):
    """The kernel ``gdn_step`` over rows ``[n, ...]`` (shapes as
    ``xla_step``'s; ``state`` the whole stack, updated in place at
    ``(layer, slots)``) -> ``(o [n, H, dv] float32, state)``."""
    n, h, dk = q.shape
    dv = v.shape[-1]
    hp, dv2 = h // 2, 2 * dv
    wide = lambda a: jnp.broadcast_to(                      # noqa: E731
        a.astype(_F32)[..., None], (n, h, dv2))
    row = lambda *tail: pl.BlockSpec(                       # noqa: E731
        (1,) + tail, lambda i, sl, ly: (i,) + (0,) * len(tail))
    st = pl.BlockSpec((1, 1, hp, dk, dv2),
                      lambda i, sl, ly: (ly[0], sl[i], 0, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, dv=dv),
        name="gdn_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n,),
            in_specs=[row(h, dk), row(h, dk), row(hp, dv2), row(h, dv2),
                      row(h, dv2), st],
            out_specs=[row(hp, dv2), st]),
        out_shape=[jax.ShapeDtypeStruct((n, hp, dv2), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},
        compiler_params=_params("arbitrary"),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q, k, v.reshape(n, hp, dv2), wide(g), wide(beta), state)
    return o.reshape(n, h, dv), state


def _chunk_kernel(slots_ref, layer_ref, fresh_ref, q_ref, k_ref, v_ref,
                  g_ref, b_ref, s_ref, o_ref, s_out, acc, *, dk: int,
                  dv: int):
    """Grid (row, pair, chunk): the pair's state stays in ``acc`` ``[128, 2
    dv]`` (its keys padded to the lanes' 128) over the row's chunks; each
    head of the pair runs ``kda._chunk_fwd`` over the pair's whole lanes
    with the other head's values and state masked to zero, so the two
    results add."""
    del slots_ref, layer_ref
    r, c = pl.program_id(0), pl.program_id(2)
    dkp, dv2 = acc.shape
    cs = kda.CHUNK

    @pl.when(c == 0)
    def _enter():
        acc[...] = jnp.zeros_like(acc)

        @pl.when(fresh_ref[r] == 0)
        def _carried():
            acc[0:dk, :] = s_ref[0, 0, 0]

    left = jax.lax.broadcasted_iota(jnp.int32, (1, dv2), 1) < dv
    s0 = acc[...]
    vv = v_ref[0]                                           # [C, 2 dv]
    o, s1 = 0.0, 0.0
    for h in range(2):
        mine = left if h == 0 else jnp.logical_not(left)
        gk = jnp.broadcast_to(kda._to_col(g_ref[0, h, pl.ds(c, 1), :]),
                              (cs, dkp))
        oh, sh, _ = kda._chunk_fwd(
            q_ref[0, :, h * dkp:(h + 1) * dkp],
            k_ref[0, :, h * dkp:(h + 1) * dkp],
            jnp.where(mine, vv, jnp.zeros_like(vv)), gk,
            b_ref[0, h, pl.ds(c, 1), :], jnp.where(mine, s0, 0.0),
            dk ** -0.5)
        o, s1 = o + oh, s1 + sh
    o_ref[0] = o
    acc[...] = s1

    @pl.when(c + 1 == pl.num_programs(2))
    def _leave():
        s_out[0, 0, 0] = acc[0:dk, :]


def pallas_chunk(q, k, v, g, beta, state, layer, slots, fresh, row_len):
    """The kernel ``gdn_chunk`` over rows ``[n, w, ...]`` (shapes as
    ``xla_chunk``'s, ``w`` a multiple of ``kda.CHUNK``; ``state`` the whole
    stack, updated in place at ``(layer, slots)``; ``fresh`` rows enter at
    zero) -> ``(o [n, w, H, dv] float32, state)``."""
    n, w, h, dk = q.shape
    dv = v.shape[-1]
    hp, dv2, cs, nc = h // 2, 2 * dv, kda.CHUNK, w // kda.CHUNK
    g, beta = _masked(g, beta, row_len)
    keys = lambda a: jnp.pad(                               # noqa: E731
        a, ((0, 0),) * 3 + ((0, _LANES - dk),)).reshape(n, w, h * _LANES)
    # [n, H, chunks, C]: a (row, pair)'s block stays in VMEM over the chunks
    rows = lambda a: jnp.transpose(                         # noqa: E731
        a.reshape(n, nc, cs, h), (0, 3, 1, 2))
    cols = lambda d: pl.BlockSpec(                          # noqa: E731
        (1, cs, d), lambda i, j, c, sl, ly, fr: (i, c, j))
    gate = pl.BlockSpec((1, 2, nc, cs),
                        lambda i, j, c, sl, ly, fr: (i, j, 0, 0))
    st = pl.BlockSpec((1, 1, 1, dk, dv2),
                      lambda i, j, c, sl, ly, fr: (ly[0], sl[i], j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, dk=dk, dv=dv),
        name="gdn_chunk",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n, hp, nc),
            in_specs=[cols(2 * _LANES), cols(2 * _LANES), cols(dv2), gate,
                      gate, st],
            out_specs=[cols(dv2), st],
            scratch_shapes=[pltpu.VMEM((_LANES, dv2), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((n, w, h * dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},
        compiler_params=_params("arbitrary", "arbitrary", "arbitrary"),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32), keys(q), keys(k), v.reshape(n, w, h * dv),
      rows(g), rows(beta), state)
    return o.reshape(n, w, h, dv), state


# ---------------------------------------------------------------------------
# the entries: rows against the slots of a state stack
# ---------------------------------------------------------------------------
def gdn_step_rows(q, k, v, g, beta, state, layer, slots):
    """The decode rows: one token each against the state at ``(layer,
    slots)`` of ``state`` ``[layers, slots + 1, ...]`` (``pack_state``'s
    layout), which is left updated. A dead row has ``slots`` 0, the null
    slot. -> ``(o [n, H, dv] float32, state)``."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[-1]
    path = gdn_path(h, dk, dv)
    _count("step_calls", path)
    if path == "pallas":
        return pallas_step(q, k, v, g, beta, state, layer, slots)
    o, s = xla_step(q, k, v, g, beta, unpack_state(state[layer, slots], h))
    return o, state.at[layer, slots].set(pack_state(s))


def gdn_chunk_rows(q, k, v, g, beta, state, layer, slots, fresh, row_len):
    """The chunk rows: ``w`` tokens each from the state at ``(layer,
    slots)``, or from zero where ``fresh``; positions at and past
    ``row_len`` change nothing. -> ``(o [n, w, H, dv] float32, state)``."""
    w, h, dk, dv = q.shape[1], q.shape[2], q.shape[3], v.shape[-1]
    path = gdn_path(h, dk, dv) if w % kda.CHUNK == 0 else "xla"
    _count("chunk_calls", path)
    if path == "pallas":
        return pallas_chunk(q, k, v, g, beta, state, layer, slots, fresh,
                            row_len)
    s0 = jnp.where(fresh[:, None, None, None], 0.0,
                   unpack_state(state[layer, slots], h))
    o, s1 = xla_chunk(q, k, v, g, beta, s0, row_len)
    return o, state.at[layer, slots].set(pack_state(s1))


def conv_step(x, taps, hist):
    """One token a row of the depthwise causal convolution of ``taps`` ``[T,
    C]``: ``x`` ``[n, C]`` after the ``T - 1`` positions ``hist`` ``[T - 1, n,
    C]`` (the oldest first): ``y = sum_j taps[j] hist_j + taps[T - 1] x``,
    float32 (the last tap multiplies the token itself, fla
    ``ShortConvolution``). Every operand is ``[n, C]``, the rows on the
    sublanes. -> ``(y [n, C] float32, the history the rows leave)``."""
    wf = taps.astype(_F32)
    y = x.astype(_F32) * wf[-1] + sum(
        hist[j].astype(_F32) * wf[j] for j in range(hist.shape[0]))
    return y, jnp.concatenate([hist[1:], x[None].astype(hist.dtype)])


def conv_rows(x, taps, hist, row_len):
    """``conv_step`` over rows of ``t`` tokens, ``x`` ``[n, t, C]``: ``y_t =
    sum_j taps[j] x_{t - (T - 1 - j)}`` with ``hist`` ``[T - 1, n, C]`` the
    positions before the rows' first (zeros before a sequence's first
    token). -> ``(y [n, t, C] float32, the history ``[T - 1, n, C]`` a row of
    ``row_len`` tokens leaves)``."""
    nt, t = taps.shape[0], x.shape[1]
    cat = jnp.concatenate([jnp.moveaxis(hist, 0, 1).astype(x.dtype), x], 1)
    wf = taps.astype(_F32)
    y = sum(cat[:, j:j + t].astype(_F32) * wf[j] for j in range(nt))
    left = jax.vmap(lambda c, n: jax.lax.dynamic_slice_in_dim(
        c, n, nt - 1, 0))(cat, row_len)
    return y, jnp.moveaxis(left, 1, 0)
