"""The gated delta rule **served** (Gated DeltaNet, arXiv:2412.06464;
``fla.layers.GatedDeltaNet``): a head keeps a state ``S`` in ``R^{dk x dv}``
that lives in a slot of a pool between ticks::

    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(dk)

``g <= 0`` a head (a scalar: ``ops/kda.py``'s recurrence with the decay equal
over a head's key channels, which commutes with the reflection), ``beta`` in
[0, 2]. ``ops/kda.py`` is the rule trained: every sequence starts at zero and
only ``o`` leaves. Here rows enter with their slot's state and leave it
behind.

**Two gate forms are served**, told apart by ``g``'s rank where the program
is traced, each with its own kernels, so that one model's program never
holds the other's. ``g`` ``[.., H]``, **a decay a head** (Gated DeltaNet,
Olmo-Hybrid), as above. ``g`` ``[.., H, dk]``, **a decay a key channel**
(Kimi Delta Attention, arXiv:2510.26692, Ling-3.0's linear layers)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T

which is ``ops/kda.py``'s rule itself: the decay scales the state's rows
before the reflection reads them. The entries:

``gdn_step_rows``   the decode rows, one token against a state (read it,
                    write it: bound by HBM)
``gdn_chunk_rows``  the chunk rows, ``w`` tokens from the slot's state
                    (``ops/kda._chunk_fwd``, the body the trained scan runs,
                    with the gate broadcast); positions at and past a row's
                    length are the identity (``beta = 0, g = 0``)
``gdn_prep_rows``   what lies between a layer's projections and those two,
                    for either row group: the depthwise causal convolution
                    after the ``taps - 1`` positions a slot carries, SiLU,
                    q's and k's l2norm a head; the slots' history is left
                    holding the positions before the rows' next token
                    (``conv_step``, ``conv_rows`` and ``normed`` are its
                    ``jax.numpy`` parts)

The kernels: ``gdn_step`` and ``gdn_chunk`` (a decay a head, heads in
pairs), ``kda_step`` and ``kda_chunk`` (a decay a channel, a head alone).

**The state's layout** is this file's, held by ``serving.paged_cache.
StatePools``: ``[layers, slots + 1, heads / 2, dk, 2 dv]`` float32, two
heads' values side by side on the lanes (``pack_state``). 192 is not a
multiple of a tile's 128 lanes and 384 is: a head alone would be stored and
moved at 256. Heads whose values fill whole tiles (``dv % 128 == 0``:
Ling-3.0's 128 x 128) lie each alone, ``[layers, slots + 1, heads, dk,
dv]``. The history's is ``[layers, taps - 1, rows, C]`` in the pools'
type, a slot a row, ``rows`` whole tiles (``conv_slot_rows``). Slot 0 is the
null slot, as page 0 is the null page: a dead row (``slots`` 0: an empty
slot, one still prefilling) reads and writes it and no tenant's state is
touched. Both stacks are updated in place.

**Two spellings of each, picked where the program is traced** (``gdn_path``,
``prep_path``: the TPU as the target, no auto mesh, sizes that fit: an even
number of heads and pairs that fill the lanes; rows and slots of whole
tiles and columns that cut into whole heads on whole lanes), never by an
argument, and counted there in ``gdn/step_calls{path=}``,
``gdn/chunk_calls{path=}`` and ``gdn/prep_calls{path=}``. The ``jax.numpy``
spelling (``xla_step``, ``xla_chunk``, ``xla_prep``) is the kernels'
reference and the path off the chip. The kernels (``gdn_step``,
``gdn_chunk``) reach a row's slot by a scalar-prefetched index, so nothing
gathers or scatters a state outside them. ``gdn_chunk`` pads the keys to
128 channels (exact: a zero channel adds nothing) and runs a pair's two
heads over the pair's whole lanes with the other head's masked to zero, so
every slice it takes is a tile's. Both chunk kernels read the rows' lengths
and skip the rule for a row of no tokens (the chunk row of a tick without a
chunk, most ticks of a window that decodes): zeros out, its slot's state as
it was.

**The pass between projections and rule** (``gdn_prep_step``,
``gdn_prep_chunk``; PR 46) takes a layer's whole history ``[taps - 1, rows,
C]`` through VMEM a column tile at a time (3.3 MB in bf16 at 48 rows of
11,520 channels) and writes it back in place, because a bf16 row of a tiled
array is no DMA's unit and a gather or scatter of rows by slot outside a
kernel cost 9 and 33 us for 0.9 MB (PERF.md section 6, PR 46). The decode
rows run **in slot space**: their tokens go to their slots' rows by an exact
0/1 product on the MXU, the chain runs beside the history as it lies, the
history moves up a tap where a slot has a row (a select), and the outputs
come back to the rows by the product the other way; a dead row brings
nothing, takes zeros and leaves the null slot as it was. A chunk row's
tokens lie on the sublanes under its slot's history (``ops/kda_prep``'s
halo) and the ``taps - 1`` positions before ``row_len`` are selected into
its slot's rows; a row of no tokens (the chunk row of a tick without a
chunk) skips the chain, keeps its projections as its outputs (nothing
reads them) and holds its blocks at one column tile, so such a call moves
one tile and not the layer's 18 MB. The l2norm's sums over a head's 96 columns, which
fill no lanes, are one 0/1 product over groups of ``lcm(dk, 128)`` columns.
The chain is float32 and rounds to the activations' type once, at the
output (the spelling: after the SiLU and after the norm).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kda, kda_prep
from .flash_attention import _interpret

__all__ = ["gdn_step_rows", "gdn_chunk_rows", "gdn_prep_rows", "conv_step",
           "conv_rows", "normed", "gdn_path", "prep_path", "conv_slot_rows",
           "pack_state", "unpack_state", "xla_step", "xla_chunk", "xla_prep",
           "pallas_step", "pallas_chunk", "pallas_prep", "gdn_recurrent",
           "kda_path", "pallas_kda_step", "pallas_kda_chunk"]

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
_VMEM_LIMIT = 96 * 1024 * 1024
_NORM_EPS = 1e-6    # the l2norm's, a head (fla ``l2norm``)
_SLOT_ROWS = 16     # a history's slots are whole tiles of any pool type
_HALO = 8           # float32 sublanes above a chunk row: its history's place
# The columns of a grid step of the pass (whole groups of ``lcm(dk, 128)``
# columns: whole heads on whole lanes), measured on the v5e at the hybrid
# cell's two shapes (benchmarks/gdn_prep_bench.py; PERF.md section 6)
_PREP_COLS = 2048


def _paired(heads: int, dv: int) -> bool:
    """Whether the state keeps two heads' values side by side."""
    return heads % 2 == 0 and dv % _LANES != 0


def pack_state(s):
    """``[..., heads, dk, dv]`` -> ``[..., heads / 2, dk, 2 dv]``: heads
    ``2p`` and ``2p + 1`` side by side (an odd count, or values of whole
    tiles of 128 lanes: each head alone)."""
    *lead, h, dk, dv = s.shape
    if not _paired(h, dv):
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
            and _paired(heads, dv) and dk % 8 == 0 and dk <= _LANES
            and (2 * dv) % _LANES == 0):
        return "pallas"
    return "xla"


def kda_path(heads: int, dk: int, dv: int) -> str:
    """``gdn_path`` for a decay a key channel: the kernels ``kda_step`` and
    ``kda_chunk`` take heads that lie alone, keys of one tile's 128 lanes
    and values of whole tiles."""
    from ..core.place import target_platform
    from ..distributed import context as dctx

    if (target_platform() == "tpu" and dctx.kernel_auto_axes() is None
            and dk == _LANES and dv % _LANES == 0):
        return "pallas"
    return "xla"


def _count(name: str, path: str) -> None:
    from ..profiler import metrics

    metrics.registry().counter("gdn/%s{path=%s}" % (name, path)).add(1)


# ---------------------------------------------------------------------------
# the jax.numpy spelling: pure functions of the rows' own states
# ---------------------------------------------------------------------------
def xla_step(q, k, v, g, beta, s):
    """One token a row: q, k ``[n, H, dk]``, v ``[n, H, dv]``, beta ``[n,
    H]``, g ``[n, H]`` (a decay a head) or ``[n, H, dk]`` (a key channel),
    s ``[n, H, dk, dv]`` float32 -> ``(o [n, H, dv] float32, s)``."""
    hi = functools.partial(jnp.einsum, precision=_HIGHEST)
    qf, kf, vf = (a.astype(_F32) for a in (q, k, v))
    dec = jnp.exp(g.astype(_F32))
    if g.ndim == 3:         # the state's rows decay, then the reflection
        s = dec[..., None] * s
        w = beta.astype(_F32)[..., None] * (vf - hi("nhk,nhkv->nhv", kf, s))
        s = s + kf[..., :, None] * w[..., None, :]
        return hi("nhk,nhkv->nhv", qf, s) * q.shape[-1] ** -0.5, s
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
    """``g`` ``[n, w, H]`` or ``[n, w, H, dk]`` and ``beta`` ``[n, w, H]``
    float32 with the positions at and past each row's length made the
    identity, ``g`` at ``kda.G_MIN`` or above (the chunked form's floor)."""
    w = g.shape[1]
    keep = (jnp.arange(w, dtype=jnp.int32)[None, :] < row_len[:, None])[
        ..., None]
    return (jnp.where(keep.reshape(keep.shape + (1,) * (g.ndim - 3)),
                      jnp.maximum(g.astype(_F32), kda.G_MIN), 0.0),
            jnp.where(keep, beta.astype(_F32), 0.0))


def xla_chunk(q, k, v, g, beta, s0, row_len):
    """``w`` tokens a row from ``s0``: q, k ``[n, w, H, dk]``, v ``[n, w, H,
    dv]``, beta ``[n, w, H]``, g ``[n, w, H]`` or, a key channel, ``[n, w,
    H, dk]``, s0 ``[n, H, dk, dv]`` float32, row_len ``[n]`` -> ``(o [n, w,
    H, dv] float32, s1)``. A ``lax.scan`` over chunks of ``kda.CHUNK``
    tokens of ``kda._chunk_fwd`` under ``vmap``."""
    n, w, h, dk = q.shape
    c = kda.CHUNK
    g, beta = _masked(g, beta, row_len)
    pad = -w % c
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                   for a in (g, beta))
    gk = g if g.ndim == 4 else jnp.broadcast_to(g[..., None],
                                                g.shape + (dk,))
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


def _chunk_kernel(slots_ref, layer_ref, fresh_ref, len_ref, q_ref, k_ref,
                  v_ref, g_ref, b_ref, s_ref, o_ref, s_out, acc, *, dk: int,
                  dv: int):
    """Grid (row, pair, chunk): the pair's state stays in ``acc`` ``[128, 2
    dv]`` (its keys padded to the lanes' 128) over the row's chunks; each
    head of the pair runs ``kda._chunk_fwd`` over the pair's whole lanes
    with the other head's values and state masked to zero, so the two
    results add. A row of no tokens (the chunk row of a tick without a
    chunk) skips the rule, as ``_kda_chunk_kernel`` does: zeros out, its
    slot's state as it was."""
    del slots_ref, layer_ref
    r, c = pl.program_id(0), pl.program_id(2)
    dkp, dv2 = acc.shape
    cs = kda.CHUNK
    some = len_ref[r] > 0

    @pl.when(c == 0)
    def _enter():
        acc[...] = jnp.zeros_like(acc)

        @pl.when(fresh_ref[r] == 0)
        def _carried():
            acc[0:dk, :] = s_ref[0, 0, 0]

    @pl.when(some)
    def _rule():
        left = jax.lax.broadcasted_iota(jnp.int32, (1, dv2), 1) < dv
        s0 = acc[...]
        vv = v_ref[0]                                       # [C, 2 dv]
        o, s1 = 0.0, 0.0
        for h in range(2):
            mine = left if h == 0 else jnp.logical_not(left)
            gk = jnp.broadcast_to(
                kda._to_col(g_ref[0, h, pl.ds(c, 1), :]), (cs, dkp))
            oh, sh, _ = kda._chunk_fwd(
                q_ref[0, :, h * dkp:(h + 1) * dkp],
                k_ref[0, :, h * dkp:(h + 1) * dkp],
                jnp.where(mine, vv, jnp.zeros_like(vv)), gk,
                b_ref[0, h, pl.ds(c, 1), :], jnp.where(mine, s0, 0.0),
                dk ** -0.5)
            o, s1 = o + oh, s1 + sh
        o_ref[0] = o
        acc[...] = s1

    @pl.when(jnp.logical_not(some))
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(c + 1 == pl.num_programs(2))
    def _leave():
        s_out[0, 0, 0] = jnp.where(some, acc[0:dk, :], s_ref[0, 0, 0])


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
        (1, cs, d), lambda i, j, c, sl, ly, fr, ln: (i, c, j))
    gate = pl.BlockSpec((1, 2, nc, cs),
                        lambda i, j, c, sl, ly, fr, ln: (i, j, 0, 0))
    st = pl.BlockSpec((1, 1, 1, dk, dv2), lambda i, j, c, sl, ly, fr, ln: (
        ly[0], sl[i], j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, dk=dk, dv=dv),
        name="gdn_chunk",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n, hp, nc),
            in_specs=[cols(2 * _LANES), cols(2 * _LANES), cols(dv2), gate,
                      gate, st],
            out_specs=[cols(dv2), st],
            scratch_shapes=[pltpu.VMEM((_LANES, dv2), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((n, w, h * dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={9: 1},
        compiler_params=_params("arbitrary", "arbitrary", "arbitrary"),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32), row_len.astype(jnp.int32), keys(q), keys(k),
      v.reshape(n, w, h * dv), rows(g), rows(beta), state)
    return o.reshape(n, w, h, dv), state


def _kda_step_kernel(slots_ref, layer_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                     s_ref, o_ref, s_out):
    """Grid (row,): every head of the row, one after the other, a head's
    state ``[dk, dv]`` alone on whole tiles. ``S' = Diag(e^g) S``, ``S' += k
    (beta (v - k^T S'))^T``, ``o = S'^T q``, all on the vector unit in
    float32; keys, queries and decays are columns over the state's rows."""
    del slots_ref, layer_ref
    heads, dk = s_ref.shape[2:4]
    scale = dk ** -0.5
    col = lambda ref, h: kda._to_col(                       # noqa: E731
        ref[0, h:h + 1, :].astype(_F32))                    # [dk, 1]
    for h in range(heads):
        s = jnp.exp(col(g_ref, h)) * s_ref[0, 0, h]
        kcol = col(k_ref, h)
        ks = jnp.sum(kcol * s, axis=0, keepdims=True)       # [1, dv]
        s = s + kcol * (b_ref[0, h:h + 1, :]
                        * (v_ref[0, h:h + 1, :].astype(_F32) - ks))
        s_out[0, 0, h] = s
        o_ref[0, h:h + 1, :] = scale * jnp.sum(col(q_ref, h) * s, axis=0,
                                               keepdims=True)


def pallas_kda_step(q, k, v, g, beta, state, layer, slots):
    """The kernel ``kda_step`` over rows ``[n, ...]`` (shapes as
    ``xla_step``'s with ``g`` ``[n, H, dk]``; ``state`` the whole stack
    ``[layers, slots + 1, H, dk, dv]``, updated in place at ``(layer,
    slots)``) -> ``(o [n, H, dv] float32, state)``."""
    n, h, dk = q.shape
    dv = v.shape[-1]
    row = lambda *tail: pl.BlockSpec(                       # noqa: E731
        (1,) + tail, lambda i, sl, ly: (i,) + (0,) * len(tail))
    st = pl.BlockSpec((1, 1, h, dk, dv),
                      lambda i, sl, ly: (ly[0], sl[i], 0, 0, 0))
    return tuple(pl.pallas_call(
        _kda_step_kernel,
        name="kda_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n,),
            in_specs=[row(h, dk), row(h, dk), row(h, dv), row(h, dk),
                      row(h, dv), st],
            out_specs=[row(h, dv), st]),
        out_shape=[jax.ShapeDtypeStruct((n, h, dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},
        compiler_params=_params("arbitrary"),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q, k, v, g.astype(_F32),
      jnp.broadcast_to(beta.astype(_F32)[..., None], (n, h, dv)), state))


def _kda_chunk_kernel(slots_ref, layer_ref, fresh_ref, len_ref, q_ref, k_ref,
                      v_ref, g_ref, b_ref, s_ref, o_ref, s_out, acc):
    """Grid (row, head, chunk): the head's state stays in ``acc`` ``[dk,
    dv]`` over the row's chunks, each ``kda._chunk_fwd`` as the trained scan
    runs it, the decay a channel as it comes. A row of no tokens (the chunk
    row of a tick without a chunk: every tick of a window that only
    decodes) skips the rule: zeros out, its slot's state as it was."""
    del slots_ref, layer_ref
    r, c = pl.program_id(0), pl.program_id(2)
    some = len_ref[r] > 0

    @pl.when(c == 0)
    def _enter():
        acc[...] = jnp.zeros_like(acc)

        @pl.when(fresh_ref[r] == 0)
        def _carried():
            acc[...] = s_ref[0, 0, 0]

    @pl.when(some)
    def _rule():
        o, s1, _ = kda._chunk_fwd(q_ref[0], k_ref[0], v_ref[0], g_ref[0],
                                  b_ref[0, 0, pl.ds(c, 1), :], acc[...],
                                  acc.shape[0] ** -0.5)
        o_ref[0] = o
        acc[...] = s1

    @pl.when(jnp.logical_not(some))
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(c + 1 == pl.num_programs(2))
    def _leave():
        s_out[0, 0, 0] = jnp.where(some, acc[...], s_ref[0, 0, 0])


def pallas_kda_chunk(q, k, v, g, beta, state, layer, slots, fresh, row_len):
    """The kernel ``kda_chunk`` over rows ``[n, w, ...]`` (shapes as
    ``xla_chunk``'s with ``g`` ``[n, w, H, dk]``, ``w`` a multiple of
    ``kda.CHUNK``; ``state`` the whole stack ``[layers, slots + 1, H, dk,
    dv]``, updated in place at ``(layer, slots)``; ``fresh`` rows enter at
    zero) -> ``(o [n, w, H, dv] float32, state)``."""
    n, w, h, dk = q.shape
    dv = v.shape[-1]
    cs, nc = kda.CHUNK, w // kda.CHUNK
    g, beta = _masked(g, beta, row_len)
    flat = lambda a: a.reshape(n, w, -1)                    # noqa: E731
    cols = lambda d: pl.BlockSpec(                          # noqa: E731
        (1, cs, d), lambda i, j, c, sl, ly, fr, ln: (i, c, j))
    gate = pl.BlockSpec((1, 1, nc, cs),
                        lambda i, j, c, sl, ly, fr, ln: (i, j, 0, 0))
    st = pl.BlockSpec((1, 1, 1, dk, dv), lambda i, j, c, sl, ly, fr, ln: (
        ly[0], sl[i], j, 0, 0))
    o, state = pl.pallas_call(
        _kda_chunk_kernel,
        name="kda_chunk",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n, h, nc),
            in_specs=[cols(dk), cols(dk), cols(dv), cols(dk), gate, st],
            out_specs=[cols(dv), st],
            scratch_shapes=[pltpu.VMEM((dk, dv), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((n, w, h * dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={9: 1},
        compiler_params=_params("arbitrary", "arbitrary", "arbitrary"),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32), row_len.astype(jnp.int32), flat(q), flat(k),
      flat(v), flat(g),
      jnp.transpose(beta.reshape(n, nc, cs, h), (0, 3, 1, 2)), state)
    return o.reshape(n, w, h, dv), state


# ---------------------------------------------------------------------------
# the entries: rows against the slots of a state stack
# ---------------------------------------------------------------------------
def gdn_step_rows(q, k, v, g, beta, state, layer, slots):
    """The decode rows: one token each against the state at ``(layer,
    slots)`` of ``state`` ``[layers, slots + 1, ...]`` (``pack_state``'s
    layout), which is left updated; ``g`` ``[n, H]`` a decay a head or ``[n,
    H, dk]`` a key channel. A dead row has ``slots`` 0, the null
    slot. -> ``(o [n, H, dv] float32, state)``."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[-1]
    channel = g.ndim == 3           # a decay a key channel (KDA)
    path = (kda_path if channel else gdn_path)(h, dk, dv)
    _count("step_calls", path)
    if path == "pallas":
        return (pallas_kda_step if channel else pallas_step)(
            q, k, v, g, beta, state, layer, slots)
    o, s = xla_step(q, k, v, g, beta, unpack_state(state[layer, slots], h))
    return o, state.at[layer, slots].set(pack_state(s))


def gdn_chunk_rows(q, k, v, g, beta, state, layer, slots, fresh, row_len):
    """The chunk rows: ``w`` tokens each from the state at ``(layer,
    slots)``, or from zero where ``fresh``; positions at and past
    ``row_len`` change nothing. -> ``(o [n, w, H, dv] float32, state)``."""
    w, h, dk, dv = q.shape[1], q.shape[2], q.shape[3], v.shape[-1]
    channel = g.ndim == 4           # a decay a key channel (KDA)
    path = (kda_path if channel else gdn_path)(h, dk, dv) \
        if w % kda.CHUNK == 0 else "xla"
    _count("chunk_calls", path)
    if path == "pallas":
        return (pallas_kda_chunk if channel else pallas_chunk)(
            q, k, v, g, beta, state, layer, slots, fresh, row_len)
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


# ---------------------------------------------------------------------------
# between a layer's projections and its rule: the short convolution over a
# carried history, SiLU, and q's and k's l2norm a head
# ---------------------------------------------------------------------------
def conv_slot_rows(num_slots: int) -> int:
    """The rows of a history ``[.., taps - 1, rows, C]`` that holds
    ``num_slots`` slots and the null slot: whole tiles of any pool type, so
    that a layer's block passes through VMEM as it lies and the 0/1 products
    over its rows meet no padding (the rows past ``num_slots`` are never
    written and stay zero)."""
    return -(-(num_slots + 1) // _SLOT_ROWS) * _SLOT_ROWS


def normed(y, heads: int, dk: int, dtype):
    """The convolution's output ``y`` ``[..., C]`` float32 (``C`` = ``[q
    heads x dk | k heads x dk | v heads x dv]``) through SiLU, its q and k
    columns ``l2norm``-ed a head (``ops/kda_prep``'s sums over a head's
    columns: the activations keep their heads side by side), rounded to
    ``dtype`` after the SiLU and after the norm. -> ``(q, k, v)``, each
    ``[..., heads, d]``."""
    y = jax.nn.silu(y).astype(dtype)
    kw = heads * dk

    def l2(a):
        af = a.astype(_F32)
        inv = jax.lax.rsqrt(kda_prep.head_sums(af * af, heads) + _NORM_EPS)
        return (af * kda_prep.over_heads(inv, dk)).astype(a.dtype)

    return _heads_of(l2(y[..., :kw]), l2(y[..., kw:2 * kw]), y[..., 2 * kw:],
                     heads)


def _heads_of(q, k, v, heads: int):
    split = lambda a: a.reshape(                            # noqa: E731
        a.shape[:-1] + (heads, a.shape[-1] // heads))
    return split(q), split(k), split(v)


def xla_prep(x, taps, conv, layer, slots, fresh, row_len, heads: int,
             dk: int):
    """``gdn_prep_rows`` in ``jax.numpy``: the history's rows gathered and
    scattered a tap at a time, rows ``[n, C]`` by slot (what a gather or
    scatter by slot moves as they lie; PERF.md section 6, PR 44),
    ``conv_step`` or ``conv_rows`` and ``normed`` between them."""
    hist = jnp.stack([conv[layer, j, slots] for j in range(conv.shape[1])])
    if fresh is not None:
        hist = jnp.where(fresh[None, :, None], 0, hist)
    y, left = conv_step(x, taps, hist) if x.ndim == 2 \
        else conv_rows(x, taps, hist, row_len)
    for j in range(conv.shape[1]):
        conv = conv.at[layer, j, slots].set(left[j].astype(conv.dtype))
    return normed(y, heads, dk, x.dtype) + (conv,)


def _prep_cols(heads: int, dk: int, dv: int):
    """``(group, tile)``: the columns the pass norms at a time (whole heads
    on whole lanes) and those of a grid step (whole groups, of q and k or of
    v alone); ``None`` where the widths have no such cut."""
    g = math.lcm(dk, _LANES)
    both = math.gcd(2 * heads * dk, heads * dv)
    if both % g:
        return None
    return g, max(t for t in range(g, both + 1, g)
                  if both % t == 0 and t <= max(_PREP_COLS, g))


def prep_path(x_shape, conv_shape, heads: int, dk: int) -> str:
    """``"pallas"`` or ``"xla"`` for rows ``x_shape`` against a history of
    ``conv_shape`` traced here: the TPU as the target, no auto mesh, rows
    and slots of whole sublane tiles, columns that cut into whole heads on
    whole lanes, a history the chunk rows' halo holds."""
    from ..core.place import target_platform
    from ..distributed import context as dctx

    c = x_shape[-1]
    dv = (c - 2 * heads * dk) // heads
    if (target_platform() == "tpu" and dctx.kernel_auto_axes() is None
            and x_shape[-2] % 8 == 0 and conv_shape[2] % _SLOT_ROWS == 0
            and conv_shape[1] <= _HALO and dv > 0
            and _prep_cols(heads, dk, dv) is not None):
        return "pallas"
    return "xla"


def _segments(g: int, dk: int, dtype):
    """0/1 ``[g, g]``: columns of one head. A row of squares times it is
    every column's own head's sum (``kda_prep.head_sums`` and ``over_heads``
    in one product)."""
    head = jnp.arange(g, dtype=jnp.int32) // dk
    return (head[:, None] == head[None, :]).astype(dtype)


def _silu_norm(y, seg_ref, norm: bool):
    """``y`` float32 ``[m, g]`` through SiLU and, where ``norm``, the l2norm
    a head: the heads' sums of squares as one 0/1 product on the MXU, of
    float32 operands under test and of the squares split ``hi + lo`` into
    the activations' type on the chip (2^-17 of a sum; the reference's own
    product takes the squares at one pass)."""
    a = y * jax.nn.sigmoid(y)
    if not norm:
        return a
    a2, seg = a * a, seg_ref[...]
    if seg.dtype == _F32:
        sums = kda._mm(a2, seg, kda._NN, _F32)
    else:
        hi = a2.astype(seg.dtype)
        sums = kda._mm(hi, seg, kda._NN, seg.dtype) + kda._mm(
            a2 - hi.astype(_F32), seg, kda._NN, seg.dtype)
    return a * jax.lax.rsqrt(sums + _NORM_EPS)


def _prep_step_kernel(layer_ref, to_rows, to_slots, has_ref, live_ref,
                      seg_ref, x_ref, w_ref, c_ref, y_ref, c_out, *,
                      group: int, norm_tiles: int):
    """Grid (column tile,): the decode rows **in slot space**. The rows'
    tokens go to their slots' rows by a 0/1 product (``to_slots`` ``[S, n]``,
    exact: one term a sum), the chain runs on ``[S, group]`` beside the
    layer's history as it lies, the history moves up a tap where a slot has
    a row, and the outputs come back to the rows by the product the other
    way (``to_rows`` ``[n, S]``). A dead row (the null slot) brings nothing
    and takes zeros; the null slot's rows are never written."""
    del layer_ref
    dt, cdt = x_ref.dtype, c_ref.dtype
    has = has_ref[...] > 0                                   # [S, 1]
    live = live_ref[...] > 0                                 # [n, 1]

    def tile(norm):
        for c0 in range(0, x_ref.shape[1], group):
            cols = slice(c0, c0 + group)
            x = jnp.where(live, x_ref[:, cols], jnp.zeros((), dt))
            xs = kda._mm(to_slots[...], x, kda._NN, dt)     # [S, g] f32
            w = w_ref[:, cols].astype(_F32)
            h = [c_ref[0, j, :, cols].astype(_F32)
                 for j in range(c_ref.shape[1])]
            y = xs * w[-1:] + sum(h[j] * w[j:j + 1] for j in range(len(h)))
            a = _silu_norm(y, seg_ref, norm).astype(dt)
            y_ref[:, cols] = kda._mm(to_rows[...], a, kda._NN, dt).astype(dt)
            for j, new in enumerate(h[1:] + [xs]):
                c_out[0, j, :, cols] = jnp.where(has, new, h[j]).astype(cdt)

    i = pl.program_id(0)
    pl.when(i < norm_tiles)(lambda: tile(True))
    pl.when(i >= norm_tiles)(lambda: tile(False))


def _prep_chunk_kernel(layer_ref, slots_ref, fresh_ref, len_ref, seg_ref,
                       x_ref, w_ref, c_ref, y_ref, c_out, xs_ref, *,
                       group: int, norm_tiles: int):
    """Grid (column tile, row): a chunk row's tokens on the sublanes under
    ``_HALO`` rows of which the last ``taps - 1`` are its slot's history
    (zeros where ``fresh``), the taps' views read at a row's offset
    (``ops/kda_prep``'s way). The history it leaves, the ``taps - 1``
    positions before ``row_len``, is selected into its slot's rows of the
    layer's block, which stays in VMEM over the rows. A row of no tokens
    (a tick without a chunk) skips the chain and keeps its projections."""
    del layer_ref
    i, r = pl.program_id(0), pl.program_id(1)
    dt, cdt = x_ref.dtype, c_ref.dtype
    w_rows, back = x_ref.shape[1], c_ref.shape[1]
    slot, n_tok = slots_ref[r], len_ref[r]
    at = jax.lax.broadcasted_iota(jnp.int32, (c_ref.shape[2], 1), 0) == slot

    @pl.when(r == 0)
    def _enter():
        c_out[...] = c_ref[...]

    @pl.when(jnp.logical_or(n_tok > 0, slot > 0))
    def _history():
        mine = jnp.logical_and(at, fresh_ref[r] == 0)
        for j in range(back):
            xs_ref[_HALO - back + j:_HALO - back + j + 1, :] = jnp.sum(
                jnp.where(mine, c_ref[0, j].astype(_F32), 0.0), axis=0,
                keepdims=True)

    @pl.when(n_tok > 0)
    def _tokens():
        xs_ref[_HALO:_HALO + w_rows, :] = x_ref[0].astype(_F32)

    def tile(norm):
        for c0 in range(0, x_ref.shape[2], group):
            cols = slice(c0, c0 + group)
            w = w_ref[:, cols].astype(_F32)
            y = sum(xs_ref[_HALO - back + j:_HALO - back + j + w_rows, cols]
                    * w[j:j + 1] for j in range(back + 1))
            y_ref[0, :, cols] = _silu_norm(y, seg_ref, norm).astype(dt)

    @pl.when(jnp.logical_and(n_tok == 0, i == 0))
    def _empty():             # the row's blocks stay at this tile
        y_ref[...] = x_ref[...]

    pl.when(jnp.logical_and(n_tok > 0, i < norm_tiles))(lambda: tile(True))
    pl.when(jnp.logical_and(n_tok > 0, i >= norm_tiles))(lambda: tile(False))

    @pl.when(slot > 0)
    def _leave():
        # rows ``n_tok + _HALO - back ...`` of the scratch: Mosaic reads a
        # dynamic row range at a tile's edge, so two tiles and a select
        first = n_tok + _HALO - back
        edge = pl.multiple_of(first // 8 * 8, 8)
        near = xs_ref[pl.ds(edge, 16), :]
        row = jax.lax.broadcasted_iota(jnp.int32, (16, 1), 0) + edge
        for j in range(back):
            left = jnp.sum(jnp.where(row == first + j, near, 0.0), axis=0,
                           keepdims=True)
            c_out[0, j] = jnp.where(at, left, c_out[0, j].astype(
                _F32)).astype(cdt)


def pallas_prep(x, taps, conv, layer, slots, fresh, row_len, heads: int,
                dk: int):
    """The kernels ``gdn_prep_step`` (``x`` ``[n, C]``: a token a row) and
    ``gdn_prep_chunk`` (``x`` ``[n, w, C]``: ``row_len`` tokens a row, a row
    ``fresh`` at a sequence's start), whatever the platform (interpreted on
    the CPU): ``taps`` ``[T, C]``, ``conv`` the whole stack ``[layers, T - 1,
    rows, C]``, read and written in place at ``(layer, slots)``. -> ``(q,
    k ``[.., heads, dk]``, v ``[.., heads, dv]``, conv)``."""
    c = x.shape[-1]
    kw, back, s = heads * dk, conv.shape[1], conv.shape[2]
    group, tile = _prep_cols(heads, dk, (c - 2 * kw) // heads)
    kind = dict(group=group, norm_tiles=2 * kw // tile)
    seg = _segments(group, dk, x.dtype)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    slots = slots.astype(jnp.int32)
    whole = lambda a: pl.BlockSpec(                         # noqa: E731
        a.shape, lambda *_: (0,) * a.ndim)
    if x.ndim == 2:
        n = x.shape[0]
        hot = (slots[:, None] == jnp.arange(s, dtype=jnp.int32)[None, :]) \
            & (slots[:, None] > 0)                          # [n, S]
        sides = (hot.astype(x.dtype), hot.T.astype(x.dtype),
                 jnp.any(hot, 0)[:, None].astype(_F32),
                 (slots[:, None] > 0).astype(_F32), seg)
        cut = lambda rows: pl.BlockSpec(                    # noqa: E731
            (rows, tile), lambda i, ly: (0, i))
        hist = pl.BlockSpec((1, back, s, tile),
                            lambda i, ly: (ly[0], 0, 0, i))
        y, conv = pl.pallas_call(
            functools.partial(_prep_step_kernel, **kind),
            name="gdn_prep_step",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(c // tile,),
                in_specs=[whole(a) for a in sides]
                + [cut(n), cut(taps.shape[0]), hist],
                out_specs=[cut(n), hist]),
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
            input_output_aliases={len(sides) + 3: 1},
            compiler_params=_params("arbitrary"),
            interpret=_interpret(),
        )(layer, *sides, x, taps, conv)
    else:
        n, w = x.shape[:2]
        # what has nothing to do stays at the first column tile, and a block
        # whose index does not move is neither fetched nor written again: a
        # row of no tokens (the rest of its output is its input, aliased),
        # and the history where every row is dead. A tick without a chunk
        # moves one tile of each
        some = lambda sl: functools.reduce(                 # noqa: E731
            jnp.logical_or, [sl[k] > 0 for k in range(n)])
        rows_ = pl.BlockSpec(
            (1, w, tile), lambda i, r, ly, sl, fr, ln: (
                r, 0, jnp.where(ln[r] > 0, i, 0)))
        hist = pl.BlockSpec(
            (1, back, s, tile), lambda i, r, ly, sl, fr, ln: (
                ly[0], 0, 0, jnp.where(some(sl), i, 0)))
        y, conv = pl.pallas_call(
            functools.partial(_prep_chunk_kernel, **kind),
            name="gdn_prep_chunk",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(c // tile, n),
                in_specs=[whole(seg), rows_,
                          pl.BlockSpec((taps.shape[0], tile),
                                       lambda i, r, *_: (0, i)), hist],
                out_specs=[rows_, hist],
                scratch_shapes=[pltpu.VMEM((_HALO + w + 16, tile), _F32)]),
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
            input_output_aliases={5: 0, 7: 1},
            compiler_params=_params("arbitrary", "arbitrary"),
            interpret=_interpret(),
        )(layer, slots, fresh.astype(jnp.int32), row_len.astype(jnp.int32),
          seg, x, taps, conv)
    return _heads_of(y[..., :kw], y[..., kw:2 * kw], y[..., 2 * kw:],
                     heads) + (conv,)


def gdn_prep_rows(x, taps, conv, layer, slots, fresh, row_len, heads: int,
                  dk: int):
    """What lies between a linear layer's projections and its rule, for one
    group of rows: ``x`` ``[n, C]`` (the decode rows, a token each) or ``[n,
    w, C]`` (the chunk rows, ``row_len`` tokens each), ``C`` = ``[q | k |
    v]`` heads side by side, through the depthwise causal convolution of
    ``taps`` ``[T, C]`` after the ``T - 1`` positions their slots carry in
    ``conv`` ``[layers, T - 1, rows, C]`` (zeros where ``fresh``), SiLU, and
    q's and k's l2norm a head. The rows' slots of ``conv[layer]`` are left
    holding the positions before the rows' next token (a chunk row's, the
    ``T - 1`` before ``row_len``). Dead rows carry slot 0, the null slot,
    whose content no tenant reads. -> ``(q, k ``[.., heads, dk]``, v ``[..,
    heads, dv]``, conv)``."""
    path = prep_path(x.shape, conv.shape, heads, dk)
    _count("prep_calls", path)
    if path == "pallas":
        return pallas_prep(x, taps, conv, layer, slots, fresh, row_len,
                           heads, dk)
    return xla_prep(x, taps, conv, layer, slots, fresh, row_len, heads, dk)
