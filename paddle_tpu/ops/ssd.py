"""The state-space rule of Mamba-2 **served** (SSD, arXiv:2405.21060;
``transformers``' ``FalconH1Mixer``): a head keeps a state ``S`` in ``R^{P x
N}`` (``P`` the head's channels, ``N`` the state's size) that lives in a slot
of a pool between ticks::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

``A < 0``, ``dt > 0`` and ``D`` a head (scalars), ``B_t`` and ``C_t`` in
``R^N`` shared by the heads of a group. There is no delta term: the decay is a
scalar a head and the update is a plain outer product, so the chunked form
needs no triangular inverse and no floor on a token's decay (``ops/kda.G_MIN``
is the delta rule's): within a block of ``CHUNK`` tokens::

    Y = ((C B^T) o L) (dt x) + exp(cum) C S_in^T ;   L[t, s] = exp(cum_t - cum_s), s <= t
    S_out = exp(cum_last) S_in + sum_s exp(cum_last - cum_s) dt_s x_s B_s^T

with ``cum`` the running sum of ``dt A`` over the block. ``ops/gdn.py`` is the
delta rule served and none of its kernels computes this; what this file takes
from it is the short convolution's ``jax.numpy`` parts (``conv_step``,
``conv_rows``), the history's row count (``conv_slot_rows``) and the design of
its in-place pass. The entries:

``ssd_step_rows``   the decode rows, one token against a state (read it, write
                    it: 2 x 4.19 MB a row at 32 heads of 128 x 256, bound by
                    HBM)
``ssd_chunk_rows``  the chunk rows, ``w`` tokens from the slot's state, in
                    blocks of ``CHUNK``; positions at and past a row's length
                    are the identity (``dt = 0``)
``ssd_prep_rows``   what lies between a layer's projection and those two: the
                    depthwise causal convolution of ``[x | B | C]`` after the
                    ``taps - 1`` positions a slot carries, its bias, SiLU; the
                    slots' history is left holding the positions before the
                    rows' next token

**The state's layout** is ``[layers, slots + 1, heads, N, P]`` float32: a
head's state *transposed*, the state's ``N`` on the sublanes and the head's
``P`` (128) on the lanes. The step is then vector work alone, with no
transpose and no reduction across lanes: ``B`` and ``C`` are columns over the
state's rows (made once a group), ``dt x`` and ``D x`` are rows, ``y`` is a sum
down the sublanes and leaves as the row it is stored as. (``N`` on the lanes
would want ``x`` as a column a head and a lane reduction a head for ``y``: 32
transposes and 512 reductions a row.) ``paged_cache.StatePools`` holds it as
``ops/gdn.pack_state`` lays heads of whole tiles: with ``key_dim = N`` and
``value_dim = P`` (``B`` plays the delta rule's ``k``, ``x`` its ``v``, ``C``
its ``q``). Slot 0 is the null slot. The history's layout is ``ops/gdn``'s,
``[layers, taps - 1, rows, C]``.

**Two spellings of each, picked where the program is traced** (``ssd_path``,
``prep_path``: the TPU as the target, no auto mesh, sizes that tile) and
counted there in ``ssd/step_calls{path=}``, ``ssd/chunk_calls{path=}`` and
``ssd/prep_calls{path=}``. The ``jax.numpy`` spelling (``xla_step``,
``xla_chunk``, ``xla_prep``) is the kernels' reference and the path off the
chip. The kernels (``ssd_step``, ``ssd_chunk``, ``ssd_prep_step``,
``ssd_prep_chunk``) reach a row's slot by a scalar-prefetched index and write
the stack in place (aliased).

**Precision.** The state, ``dt``, ``exp(dt A)`` and every sum are float32.
The step has no product on the MXU. The chunk's products take operands of the
activations' type (bf16 on the chip: ``C B^T``, ``(C B^T o L)(dt x)``, ``B^T
(w dt x)``) with float32 accumulation; the carried state enters ``C S^T`` as
``hi + lo`` of that type (two passes: 2^-17 of a term), float32 operands at
``HIGHEST`` where the activations are float32 (the tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import gdn, kda
from .flash_attention import _interpret

__all__ = ["ssd_step_rows", "ssd_chunk_rows", "ssd_prep_rows", "ssd_path",
           "prep_path", "xla_step", "xla_chunk", "xla_prep", "pallas_step",
           "pallas_chunk", "pallas_prep", "ssd_recurrent", "CHUNK"]

_F32 = jnp.float32
_LANES = 128
_VMEM_LIMIT = 96 * 1024 * 1024
#: tokens of a block of the chunked form (``mamba_chunk_size``)
CHUNK = 128
#: the columns the pass between projection and rule works on at a time, and
#: the most of a grid step (whole groups; 5,120 channels are two steps)
_PREP_GROUP = 512
_PREP_COLS = 2560


def ssd_path(heads: int, n: int, p: int) -> str:
    """``"pallas"`` or ``"xla"`` for heads of ``p`` channels and a state of
    ``n`` traced here."""
    from ..core.place import target_platform
    from ..distributed import context as dctx

    if (target_platform() == "tpu" and dctx.kernel_auto_axes() is None
            and p % _LANES == 0 and n % 8 == 0):
        return "pallas"
    return "xla"


def _count(name: str, path: str) -> None:
    from ..profiler import metrics

    metrics.registry().counter("ssd/%s{path=%s}" % (name, path)).add(1)


def _of_heads(a, heads: int):
    """``[..., G, N]`` -> ``[..., H, N]``: every head its group's (heads
    ``g H/G .. (g + 1) H/G - 1`` are group ``g``'s)."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


# ---------------------------------------------------------------------------
# the jax.numpy spelling: pure functions of the rows' own states
# ---------------------------------------------------------------------------
def xla_step(x, B, C, dt, A, D, s):
    """One token a row: x ``[n, H, P]``, B, C ``[n, G, N]``, dt ``[n, H]``
    float32 (after the softplus), A, D ``[H]`` float32, s ``[n, H, N, P]``
    float32 -> ``(y [n, H, P] float32, s)``."""
    h = x.shape[1]
    xf, dtf = x.astype(_F32), dt.astype(_F32)
    bh, ch = (_of_heads(a.astype(_F32), h) for a in (B, C))     # [n, H, N]
    dec = jnp.exp(dtf * A.astype(_F32))
    s = dec[..., None, None] * s \
        + bh[..., :, None] * (dtf[..., None] * xf)[..., None, :]
    y = jnp.sum(ch[..., :, None] * s, axis=-2)
    return y + D.astype(_F32)[:, None] * xf, s


def ssd_recurrent(x, B, C, dt, A, D, s0):
    """The recurrence itself over ``[n, t, ...]`` from ``s0``, a token a
    step, float32: what the chunked paths must equal. -> ``(y, s1)``."""
    f = lambda a: jnp.moveaxis(a, 1, 0)                     # noqa: E731

    def step(s, row):
        y, s = xla_step(*row, A, D, s)
        return s, y

    s1, y = jax.lax.scan(step, s0.astype(_F32), (f(x), f(B), f(C), f(dt)))
    return jnp.moveaxis(y, 0, 1), s1


def _state_product(c, s0, dt):
    """``C S^T``: ``c`` ``[Q, N]`` against the carried state ``s0`` ``[N,
    P]`` float32, which meets a 16-bit product as ``hi + lo``."""
    if dt == _F32:
        return kda._mm(c, s0, kda._NN, dt)
    hi = s0.astype(dt)
    return kda._mm(c, hi, kda._NN, dt) \
        + kda._mm(c, s0 - hi.astype(_F32), kda._NN, dt)


def _chunk_body(x, b, c, dt_col, cum_col, cum_row, s0):
    """One head over one block of ``Q`` tokens: x ``[Q, P]``, b, c ``[Q,
    N]`` in the activations' type, dt ``[Q, 1]`` and ``cum`` (the running sum
    of ``dt A``, as a column and as a row) float32, ``s0`` ``[N, P]`` float32
    -> ``(y [Q, P] float32 without the skip, s1)``. The kernel's body and the
    spelling's (under ``vmap``)."""
    q, dt = x.shape[0], x.dtype
    dtx = dt_col * x.astype(_F32)                               # [Q, P]
    seen = kda._iota((q, q), 0) >= kda._iota((q, q), 1)
    # cum falls: the exponent is <= 0 wherever it is kept
    lower = jnp.where(seen, jnp.exp(jnp.where(seen, cum_col - cum_row, 0.0)),
                      0.0)
    y = kda._mm(kda._mm(c, b, kda._NT, dt) * lower, dtx, kda._NN, dt) \
        + jnp.exp(cum_col) * _state_product(c, s0, dt)
    last = cum_col[q - 1:q, :]                                  # [1, 1]
    s1 = jnp.exp(last) * s0 + kda._mm(b, jnp.exp(last - cum_col) * dtx,
                                      kda._TN, dt)
    return y, s1


def _masked_dt(dt, row_len):
    """``dt`` ``[n, w, H]`` float32 with the positions at and past each
    row's length at 0: no decay and no input, the identity."""
    keep = jnp.arange(dt.shape[1], dtype=jnp.int32)[None, :] < row_len[:, None]
    return jnp.where(keep[..., None], dt.astype(_F32), 0.0)


def _chunks(dt, A):
    """``(dt, cum)`` as ``[n, H, blocks, CHUNK]``: a (row, head)'s blocks
    side by side, ``cum`` the running sum of ``dt A`` within each block."""
    n, w, h = dt.shape
    dt = jnp.transpose(dt.reshape(n, w // CHUNK, CHUNK, h), (0, 3, 1, 2))
    return dt, jnp.cumsum(dt * A.astype(_F32)[None, :, None, None], axis=-1)


def xla_chunk(x, B, C, dt, A, D, s0, row_len):
    """``w`` tokens a row from ``s0``: x ``[n, w, H, P]``, B, C ``[n, w, G,
    N]``, dt ``[n, w, H]`` float32, A, D ``[H]``, s0 ``[n, H, N, P]`` float32,
    row_len ``[n]`` -> ``(y [n, w, H, P] float32, s1)``: a ``lax.scan`` over
    blocks of ``CHUNK`` tokens of ``_chunk_body`` under ``vmap``."""
    n, w, h, p = x.shape
    pad = -w % CHUNK
    dt = _masked_dt(dt, row_len)
    if pad:
        x, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (x, B, C))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    nc = (w + pad) // CHUNK
    dtc, cum = _chunks(dt, A)                               # [n, H, nc, Q]
    # blocks first, then rows and heads: [nc, n, H, Q, ...]
    blocks = lambda a: jnp.transpose(                       # noqa: E731
        a.reshape(n, nc, CHUNK, h, -1), (1, 0, 3, 2, 4))
    gates = lambda a: jnp.transpose(a, (2, 0, 1, 3))        # noqa: E731
    body = jax.vmap(jax.vmap(
        lambda x, b, c, d, cu, s: _chunk_body(
            x, b, c, d[:, None], cu[:, None], cu[None, :], s)))

    def step(s, blk):
        y, s1 = body(*blk, s)
        return s1, y

    s1, y = jax.lax.scan(step, s0.astype(_F32), (
        blocks(x), blocks(_of_heads(B, h)), blocks(_of_heads(C, h)),
        gates(dtc), gates(cum)))
    y = jnp.transpose(y, (1, 0, 3, 2, 4)).reshape(n, w + pad, h, p)
    return (y + D.astype(_F32)[:, None] * x.astype(_F32))[:, :w], s1


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _step_kernel(slots_ref, layer_ref, dtx_ref, dec_ref, b_ref, c_ref, s_ref,
                 o_ref, s_out):
    """Grid (row,): every head of the row, one after the other, a head's
    state ``[N, P]`` on whole tiles. ``S' = e^{dt A} S + B (dt x)^T`` and ``y
    = C^T S'``, all on the vector unit in float32: ``B`` and ``C`` are columns
    over the state's rows, made once a group; ``dt x`` and the decay come as
    rows over the lanes."""
    del slots_ref, layer_ref
    heads = s_ref.shape[2]
    groups = b_ref.shape[1]
    for g in range(groups):
        bcol = kda._to_col(b_ref[0, g:g + 1, :].astype(_F32))   # [N, 1]
        ccol = kda._to_col(c_ref[0, g:g + 1, :].astype(_F32))
        for h in range(g * heads // groups, (g + 1) * heads // groups):
            s = dec_ref[0, h:h + 1, :] * s_ref[0, 0, h] \
                + bcol * dtx_ref[0, h:h + 1, :]
            s_out[0, 0, h] = s
            o_ref[0, h:h + 1, :] = jnp.sum(ccol * s, axis=0, keepdims=True)


def pallas_step(x, B, C, dt, A, D, state, layer, slots):
    """The kernel ``ssd_step`` over rows ``[n, ...]`` (shapes as
    ``xla_step``'s; ``state`` the whole stack ``[layers, slots + 1, H, N,
    P]``, updated in place at ``(layer, slots)``) -> ``(y [n, H, P] float32,
    state)``."""
    n, h, p = x.shape
    g, ns = B.shape[1], B.shape[2]
    xf, dtf = x.astype(_F32), dt.astype(_F32)
    row = lambda *tail: pl.BlockSpec(                       # noqa: E731
        (1,) + tail, lambda i, sl, ly: (i,) + (0,) * len(tail))
    st = pl.BlockSpec((1, 1, h, ns, p),
                      lambda i, sl, ly: (ly[0], sl[i], 0, 0, 0))
    y, state = pl.pallas_call(
        _step_kernel,
        name="ssd_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n,),
            in_specs=[row(h, p), row(h, p), row(g, ns), row(g, ns), st],
            out_specs=[row(h, p), st]),
        out_shape=[jax.ShapeDtypeStruct((n, h, p), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},
        compiler_params=_params("arbitrary"),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      dtf[..., None] * xf,
      jnp.broadcast_to(jnp.exp(dtf * A.astype(_F32))[..., None], (n, h, p)),
      B, C, state)
    return y + D.astype(_F32)[:, None] * xf, state


def _chunk_kernel(slots_ref, layer_ref, fresh_ref, len_ref, x_ref, b_ref,
                  c_ref, dt_ref, cum_ref, s_ref, o_ref, s_out, acc):
    """Grid (row, head, block): the head's state stays in ``acc`` ``[N, P]``
    over the row's blocks, each ``_chunk_body``. A row of no tokens (the chunk
    row of a tick without a chunk) skips the rule: zeros out, its slot's
    state as it was."""
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
        cum = cum_ref[0, 0, pl.ds(c, 1), :]                     # [1, Q]
        y, s1 = _chunk_body(
            x_ref[0], b_ref[0], c_ref[0],
            kda._to_col(dt_ref[0, 0, pl.ds(c, 1), :]), kda._to_col(cum), cum,
            acc[...])
        o_ref[0] = y
        acc[...] = s1

    @pl.when(jnp.logical_not(some))
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(c + 1 == pl.num_programs(2))
    def _leave():
        s_out[0, 0, 0] = jnp.where(some, acc[...], s_ref[0, 0, 0])


def pallas_chunk(x, B, C, dt, A, D, state, layer, slots, fresh, row_len):
    """The kernel ``ssd_chunk`` over rows ``[n, w, ...]`` (shapes as
    ``xla_chunk``'s, ``w`` a multiple of ``CHUNK``; ``state`` the whole
    stack, updated in place at ``(layer, slots)``; ``fresh`` rows enter at
    zero) -> ``(y [n, w, H, P] float32, state)``."""
    n, w, h, p = x.shape
    g, ns = B.shape[2], B.shape[3]
    nc, per = w // CHUNK, h // g
    dtc, cum = _chunks(_masked_dt(dt, row_len), A)
    flat = lambda a: a.reshape(n, w, -1)                    # noqa: E731
    cols = lambda d, of: pl.BlockSpec(                      # noqa: E731
        (1, CHUNK, d), lambda i, j, c, sl, ly, fr, ln: (i, c, of(j)))
    head, group = (lambda j: j), (lambda j: j // per)
    gate = pl.BlockSpec((1, 1, nc, CHUNK),
                        lambda i, j, c, sl, ly, fr, ln: (i, j, 0, 0))
    st = pl.BlockSpec((1, 1, 1, ns, p), lambda i, j, c, sl, ly, fr, ln: (
        ly[0], sl[i], j, 0, 0))
    y, state = pl.pallas_call(
        _chunk_kernel,
        name="ssd_chunk",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n, h, nc),
            in_specs=[cols(p, head), cols(ns, group), cols(ns, group), gate,
                      gate, st],
            out_specs=[cols(p, head), st],
            scratch_shapes=[pltpu.VMEM((ns, p), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((n, w, h * p), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={9: 1},
        compiler_params=_params("arbitrary", "arbitrary", "arbitrary"),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32), row_len.astype(jnp.int32), flat(x), flat(B),
      flat(C), dtc, cum, state)
    return y.reshape(n, w, h, p) \
        + D.astype(_F32)[:, None] * x.astype(_F32), state


# ---------------------------------------------------------------------------
# the entries: rows against the slots of a state stack
# ---------------------------------------------------------------------------
def ssd_step_rows(x, B, C, dt, A, D, state, layer, slots):
    """The decode rows: one token each against the state at ``(layer,
    slots)`` of ``state`` ``[layers, slots + 1, H, N, P]``, which is left
    updated. A dead row has ``slots`` 0, the null slot. -> ``(y [n, H, P]
    float32, state)``."""
    path = ssd_path(x.shape[1], B.shape[-1], x.shape[-1])
    _count("step_calls", path)
    if path == "pallas":
        return pallas_step(x, B, C, dt, A, D, state, layer, slots)
    y, s = xla_step(x, B, C, dt, A, D, state[layer, slots])
    return y, state.at[layer, slots].set(s)


def ssd_chunk_rows(x, B, C, dt, A, D, state, layer, slots, fresh, row_len):
    """The chunk rows: ``w`` tokens each from the state at ``(layer,
    slots)``, or from zero where ``fresh``; positions at and past ``row_len``
    change nothing. -> ``(y [n, w, H, P] float32, state)``."""
    path = ssd_path(x.shape[2], B.shape[-1], x.shape[-1]) \
        if x.shape[1] % CHUNK == 0 else "xla"
    _count("chunk_calls", path)
    if path == "pallas":
        return pallas_chunk(x, B, C, dt, A, D, state, layer, slots, fresh,
                            row_len)
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state[layer, slots])
    y, s1 = xla_chunk(x, B, C, dt, A, D, s0, row_len)
    return y, state.at[layer, slots].set(s1)


# ---------------------------------------------------------------------------
# between a layer's projection and its rule: the short convolution over a
# carried history, its bias, SiLU
# ---------------------------------------------------------------------------
def xla_prep(x, taps, bias, conv, layer, slots, fresh, row_len):
    """``ssd_prep_rows`` in ``jax.numpy``: the history's rows gathered and
    scattered a tap at a time (``ops/gdn.xla_prep``'s way), ``gdn.conv_step``
    or ``gdn.conv_rows`` between them, then the bias and SiLU."""
    hist = jnp.stack([conv[layer, j, slots] for j in range(conv.shape[1])])
    if fresh is not None:
        hist = jnp.where(fresh[None, :, None], 0, hist)
    y, left = gdn.conv_step(x, taps, hist) if x.ndim == 2 \
        else gdn.conv_rows(x, taps, hist, row_len)
    for j in range(conv.shape[1]):
        conv = conv.at[layer, j, slots].set(left[j].astype(conv.dtype))
    return jax.nn.silu(y + bias.astype(_F32)).astype(x.dtype), conv


def _prep_cols(c: int):
    """``(group, tile)``: the columns the pass works on at a time and those
    of a grid step (whole groups); ``None`` where ``c`` is not whole lanes."""
    if c % _LANES:
        return None
    g = _PREP_GROUP if c % _PREP_GROUP == 0 else _LANES
    return g, max(t for t in range(g, c + 1, g)
                  if c % t == 0 and t <= max(_PREP_COLS, g))


def prep_path(x_shape, conv_shape) -> str:
    """``"pallas"`` or ``"xla"`` for rows ``x_shape`` against a history of
    ``conv_shape`` traced here (``ops/gdn.prep_path``'s conditions: rows and
    slots of whole sublane tiles, columns of whole lanes, a history the chunk
    rows' halo holds)."""
    from ..core.place import target_platform
    from ..distributed import context as dctx

    if (target_platform() == "tpu" and dctx.kernel_auto_axes() is None
            and x_shape[-2] % 8 == 0 and conv_shape[2] % gdn._SLOT_ROWS == 0
            and conv_shape[1] <= gdn._HALO
            and _prep_cols(x_shape[-1]) is not None):
        return "pallas"
    return "xla"


def _prep_step_kernel(layer_ref, to_rows, to_slots, has_ref, live_ref, x_ref,
                      w_ref, b_ref, c_ref, y_ref, c_out, *, group: int):
    """Grid (column tile,): the decode rows **in slot space**
    (``ops/gdn._prep_step_kernel``'s way). The rows' tokens go to their
    slots' rows by a 0/1 product (``to_slots`` ``[S, n]``, exact: one term a
    sum), the taps run on ``[S, group]`` beside the layer's history as it
    lies, the history moves up a tap where a slot has a row, and the outputs
    come back to the rows by the product the other way. A dead row (the null
    slot) brings nothing and takes ``SiLU(bias)``, which nothing reads; the
    null slot's rows are never written."""
    del layer_ref
    dt, cdt = x_ref.dtype, c_ref.dtype
    has = has_ref[...] > 0                                   # [S, 1]
    live = live_ref[...] > 0                                 # [n, 1]
    for c0 in range(0, x_ref.shape[1], group):
        cols = slice(c0, c0 + group)
        x = jnp.where(live, x_ref[:, cols], jnp.zeros((), dt))
        xs = kda._mm(to_slots[...], x, kda._NN, dt)         # [S, g] f32
        w = w_ref[:, cols].astype(_F32)
        h = [c_ref[0, j, :, cols].astype(_F32)
             for j in range(c_ref.shape[1])]
        y = xs * w[-1:] + sum(h[j] * w[j:j + 1] for j in range(len(h))) \
            + b_ref[:, cols].astype(_F32)
        a = (y * jax.nn.sigmoid(y)).astype(dt)
        y_ref[:, cols] = kda._mm(to_rows[...], a, kda._NN, dt).astype(dt)
        for j, new in enumerate(h[1:] + [xs]):
            c_out[0, j, :, cols] = jnp.where(has, new, h[j]).astype(cdt)


def _prep_chunk_kernel(layer_ref, slots_ref, fresh_ref, len_ref, x_ref, w_ref,
                       b_ref, c_ref, y_ref, c_out, xs_ref, *, group: int):
    """Grid (column tile, row): a chunk row's tokens on the sublanes under
    ``gdn._HALO`` rows of which the last ``taps - 1`` are its slot's history
    (zeros where ``fresh``), the taps' views read at a row's offset
    (``ops/gdn._prep_chunk_kernel``'s way). The history it leaves, the ``taps
    - 1`` positions before ``row_len``, is selected into its slot's rows of
    the layer's block, which stays in VMEM over the rows. A row of no tokens
    skips the taps and keeps its projection."""
    del layer_ref
    halo = gdn._HALO
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
            xs_ref[halo - back + j:halo - back + j + 1, :] = jnp.sum(
                jnp.where(mine, c_ref[0, j].astype(_F32), 0.0), axis=0,
                keepdims=True)

    @pl.when(n_tok > 0)
    def _tokens():
        xs_ref[halo:halo + w_rows, :] = x_ref[0].astype(_F32)
        for c0 in range(0, x_ref.shape[2], group):
            cols = slice(c0, c0 + group)
            w = w_ref[:, cols].astype(_F32)
            y = sum(xs_ref[halo - back + j:halo - back + j + w_rows, cols]
                    * w[j:j + 1] for j in range(back + 1)) \
                + b_ref[:, cols].astype(_F32)
            y_ref[0, :, cols] = (y * jax.nn.sigmoid(y)).astype(dt)

    @pl.when(jnp.logical_and(n_tok == 0, i == 0))
    def _empty():             # the row's blocks stay at this tile
        y_ref[...] = x_ref[...]

    @pl.when(slot > 0)
    def _leave():
        # rows ``n_tok + halo - back ...`` of the scratch: Mosaic reads a
        # dynamic row range at a tile's edge, so two tiles and a select
        first = n_tok + halo - back
        edge = pl.multiple_of(first // 8 * 8, 8)
        near = xs_ref[pl.ds(edge, 16), :]
        row = jax.lax.broadcasted_iota(jnp.int32, (16, 1), 0) + edge
        for j in range(back):
            left = jnp.sum(jnp.where(row == first + j, near, 0.0), axis=0,
                           keepdims=True)
            c_out[0, j] = jnp.where(at, left, c_out[0, j].astype(
                _F32)).astype(cdt)


def pallas_prep(x, taps, bias, conv, layer, slots, fresh, row_len):
    """The kernels ``ssd_prep_step`` (``x`` ``[n, C]``: a token a row) and
    ``ssd_prep_chunk`` (``x`` ``[n, w, C]``: ``row_len`` tokens a row, a row
    ``fresh`` at a sequence's start), whatever the platform (interpreted on
    the CPU): ``taps`` ``[T, C]``, ``bias`` ``[C]``, ``conv`` the whole stack
    ``[layers, T - 1, rows, C]``, read and written in place at ``(layer,
    slots)``. -> ``(SiLU(conv + bias) in x's shape and type, conv)``."""
    c = x.shape[-1]
    back, s = conv.shape[1], conv.shape[2]
    group, tile = _prep_cols(c)
    bias = bias.reshape(1, c)
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
                 (slots[:, None] > 0).astype(_F32))
        cut = lambda rows: pl.BlockSpec(                    # noqa: E731
            (rows, tile), lambda i, ly: (0, i))
        hist = pl.BlockSpec((1, back, s, tile),
                            lambda i, ly: (ly[0], 0, 0, i))
        return tuple(pl.pallas_call(
            functools.partial(_prep_step_kernel, group=group),
            name="ssd_prep_step",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(c // tile,),
                in_specs=[whole(a) for a in sides]
                + [cut(n), cut(taps.shape[0]), cut(1), hist],
                out_specs=[cut(n), hist]),
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
            input_output_aliases={len(sides) + 4: 1},
            compiler_params=_params("arbitrary"),
            interpret=_interpret(),
        )(layer, *sides, x, taps, bias, conv))
    n, w = x.shape[:2]
    # what has nothing to do stays at the first column tile, and a block
    # whose index does not move is neither fetched nor written again: a row
    # of no tokens (the rest of its output is its input, aliased), and the
    # history where every row is dead. A tick without a chunk moves one tile
    # of each
    some = lambda sl: functools.reduce(                     # noqa: E731
        jnp.logical_or, [sl[k] > 0 for k in range(n)])
    rows_ = pl.BlockSpec(
        (1, w, tile), lambda i, r, ly, sl, fr, ln: (
            r, 0, jnp.where(ln[r] > 0, i, 0)))
    hist = pl.BlockSpec(
        (1, back, s, tile), lambda i, r, ly, sl, fr, ln: (
            ly[0], 0, 0, jnp.where(some(sl), i, 0)))
    side = lambda rows: pl.BlockSpec(                       # noqa: E731
        (rows, tile), lambda i, r, *_: (0, i))
    return tuple(pl.pallas_call(
        functools.partial(_prep_chunk_kernel, group=group),
        name="ssd_prep_chunk",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(c // tile, n),
            in_specs=[rows_, side(taps.shape[0]), side(1), hist],
            out_specs=[rows_, hist],
            scratch_shapes=[pltpu.VMEM((gdn._HALO + w + 16, tile), _F32)]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
        input_output_aliases={4: 0, 7: 1},
        compiler_params=_params("arbitrary", "arbitrary"),
        interpret=_interpret(),
    )(layer, slots, fresh.astype(jnp.int32), row_len.astype(jnp.int32), x,
      taps, bias, conv))


def ssd_prep_rows(x, taps, bias, conv, layer, slots, fresh=None,
                  row_len=None):
    """What lies between an SSD layer's projection and its rule, for one
    group of rows: ``x`` ``[n, C]`` (the decode rows, a token each) or ``[n,
    w, C]`` (the chunk rows, ``row_len`` tokens each), ``C`` = ``[x | B |
    C]``, through the depthwise causal convolution of ``taps`` ``[T, C]``
    after the ``T - 1`` positions their slots carry in ``conv`` ``[layers, T -
    1, rows, C]`` (zeros where ``fresh``), the ``bias`` ``[C]`` and SiLU. The
    rows' slots of ``conv[layer]`` are left holding the positions before the
    rows' next token. Dead rows carry slot 0, the null slot, whose content no
    tenant reads. -> ``(the activated rows in x's shape and type, conv)``."""
    path = prep_path(x.shape, conv.shape)
    _count("prep_calls", path)
    prep = pallas_prep if path == "pallas" else xla_prep
    return prep(x, taps, bias, conv, layer, slots, fresh, row_len)
