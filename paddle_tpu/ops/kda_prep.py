"""What lies between a KDA layer's three projections and its scan
(``ops/kda.py``): the short convolution, the SiLU and, for q and k, the
``l2norm`` over each head's columns, in one pass over the projections each
way.

``kda_prep(projections, taps, norms, head_dim, eps) -> operands``: every
projection ``[b, s, heads * d]`` (heads side by side, as the scan reads
them) goes with its tap weights ``[taps, heads * d]`` through::

    y_t = sum_j taps[j] * x_{t - (n_taps - 1 - j)}      depthwise, causal, the
                                                       last tap on the token
    a   = y * sigmoid(y)
    out = a * rsqrt(sum_head(a * a) + eps)             where its ``norms`` says

in float32, rounded once to the projection's type at the output.

**Two paths, observed.** On one TPU device with no multi-device auto mesh
open, a sequence of whole sublane tiles and heads of whole lanes
(``prep_path``), all branches are the inputs and outputs of **one** Pallas
call a pass: ``kda_prep`` forward, ``kda_prep_bwd`` under a
``jax.custom_vjp`` whose residuals are the raw projections and the taps and
nothing else. The backward kernel recomputes the chain in VMEM from the
projections, takes the scan's three cotangents and writes the gradients
towards the projections (their type) and the taps' (float32 ``[taps, heads
* d]``, summed in an output block that the row and batch axes revisit).
Nothing float32 of ``[s, heads * d]`` reaches HBM either way. Everywhere
else (the CPU, a multi-device auto mesh, sizes that do not tile)
``xla_kda_prep`` spells the same chain in ``jax.numpy``; it is the kernels'
reference in tests/test_solar_open2.py, and rounds twice (after the SiLU
and after the norm) where the kernel rounds once. There is no argument,
field or variable for the path; it is counted at trace time in
``kda/prep_calls{path=}``.

**The halo.** A row block's convolution needs the ``taps - 1`` rows before
it: a second view of the same projection, one tile of ``_HALO_ROWS`` rows at
the neighbouring index, zero at the sequence's start. The transposed
convolution of the backward pass needs the ``taps - 1`` rows of ``dy`` after
the block: the backward grid walks a sequence's row blocks last first and
carries the first rows of the block it has just done in VMEM. A column tile
holds whole heads (one, at the sizes the layer has), so the norm's sums are
over lanes of one tile. Inside a block the chain runs a head's columns at a
time over all the block's rows, hundreds of vector registers a value: the
values spill to VMEM and the chain's latencies hide behind each other (16
rows at a time, two registers a value and nothing spilled, took six times as
long on the chip: every operation waited for the one before it; PERF.md
section 6). Both kernels are bound by the vector unit, at 60-65 % of what
HBM's bandwidth would allow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

__all__ = ["kda_prep", "pallas_kda_prep", "xla_kda_prep", "prep_path",
           "causal_conv", "head_sums", "over_heads"]

_F32 = jnp.float32
# One head's columns and 2,048 rows a block, measured on the v5e at the training
# cell's [1, 8192, 8192] bf16 (benchmarks/kda_prep_bench.py; PERF.md section 6):
# 1.47 ms forward and 2.40 backward; two or four heads a block 1.8 and 3.7
# whatever the rows, 512 rows of one head 1.86 and 2.64, 4,096 rows 1.39 and
# 2.40 for three times the kernel's compile time
_ROWS = 2048        # the most rows a block
_COLS = 128         # the most columns a block (whole heads: at least one)
_HALO = 8           # float32 sublanes of history kept above a block
_HALO_ROWS = 16     # the halo view's rows: one bf16 tile
_VMEM_LIMIT = 64 * 1024 * 1024


# ---------------------------------------------------------------------------
# the jax.numpy spelling: the reference, and the path off the chip
# ---------------------------------------------------------------------------
def causal_conv(x, w):
    """Depthwise causal convolution over the sequence, no bias: ``y_t =
    sum_j w[j] x_{t - (taps - 1 - j)}``; x [b, s, c], w [taps, c]. The last
    tap multiplies the token itself (fla ``ShortConvolution``)."""
    taps, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(_F32), ((0, 0), (taps - 1, 0), (0, 0)))
    wf = w.astype(_F32)
    return sum(xp[:, j:j + s] * wf[j] for j in range(taps))


def head_sums(x, heads: int):
    """Sums over each head's columns of ``x`` [b, s, heads * d] ->
    [b, s, heads] float32, as a product with a 0/1 matrix: the activations
    stay ``[b, s, heads * d]`` from the projection to the scan (a view a
    head ``[b, s, heads, d]`` is another layout on the TPU and costs a
    copy of every such array)."""
    d = x.shape[-1] // heads
    seg = (jnp.arange(heads * d)[:, None] // d
           == jnp.arange(heads)[None, :]).astype(x.dtype)
    return jnp.dot(x, seg, preferred_element_type=_F32)


def over_heads(a, d: int):
    """``a`` [b, s, heads] repeated over each head's ``d`` columns, exactly:
    the same 0/1 product the other way, at full precision."""
    heads = a.shape[-1]
    seg = (jnp.arange(heads)[:, None]
           == jnp.arange(heads * d)[None, :] // d).astype(_F32)
    return jnp.dot(a.astype(_F32), seg,
                   precision=jax.lax.Precision.HIGHEST)


def xla_kda_prep(projections, taps, norms, head_dim, eps):
    """The chain in ``jax.numpy``, a branch at a time."""
    outs = []
    for x, w, norm in zip(projections, taps, norms):
        y = jax.nn.silu(causal_conv(x, w)).astype(x.dtype)
        if norm:
            yf = y.astype(_F32)
            inv = jax.lax.rsqrt(
                head_sums(yf * yf, x.shape[-1] // head_dim) + eps)
            y = (yf * over_heads(inv, head_dim)).astype(x.dtype)
        outs.append(y)
    return tuple(outs)


# ---------------------------------------------------------------------------
# the Pallas path
# ---------------------------------------------------------------------------
def _largest(limit: int, step: int, total: int) -> int:
    """The largest multiple of ``step`` up to ``limit`` that divides
    ``total`` (``step`` does)."""
    return max(n for n in range(step, min(limit, total) + 1, step)
               if total % n == 0)


def _history(x_ref, h_ref, xs_ref, first):
    """A block in float32 under ``_HALO`` rows of what came before it
    (zeros before the sequence's start), in ``xs_ref``."""
    halo = h_ref[0].astype(_F32)[_HALO_ROWS - _HALO:]
    xs_ref[0:_HALO] = jnp.where(first, 0.0, halo)
    xs_ref[_HALO:] = x_ref[0].astype(_F32)


def _shifted(ref, cols, rows, taps, after=False):
    """The ``taps`` views of a head's ``rows`` rows that the taps multiply,
    tap ``j``'s first: each row's neighbour ``taps - 1 - j`` rows before it
    in a ``ref`` that holds ``_HALO`` rows of history above the block, or
    (``after``) as many rows after it in one that holds the block first."""
    starts = [back if after else _HALO - back
              for back in range(taps - 1, -1, -1)]
    return [ref[a:a + rows, cols] for a in starts]


def _tap_rows(w_ref, cols):
    return [w_ref[j:j + 1, cols].astype(_F32) for j in range(w_ref.shape[0])]


def _silu(y):
    sg = jax.nn.sigmoid(y)
    return y * sg, sg


def _fwd_kernel(*refs, norms, d, eps):
    n = len(norms)
    x_refs, h_refs, w_refs, o_refs = (refs[i * n:(i + 1) * n]
                                      for i in range(4))
    xs_ref, = refs[4 * n:]
    rows, width = x_refs[0].shape[1:]
    first = pl.program_id(2) == 0
    for x_ref, h_ref, w_ref, o_ref, norm in zip(x_refs, h_refs, w_refs,
                                                o_refs, norms):
        _history(x_ref, h_ref, xs_ref, first)
        for c0 in range(0, width, d):       # a head at a time
            cols = slice(c0, c0 + d)
            w_rows = _tap_rows(w_ref, cols)
            xs = _shifted(xs_ref, cols, rows, len(w_rows))
            a, _ = _silu(sum(x * w for x, w in zip(xs, w_rows)))
            if norm:
                a = a * jax.lax.rsqrt(
                    jnp.sum(a * a, axis=-1, keepdims=True) + eps)
            o_ref[0, :, cols] = a.astype(o_ref.dtype)


def _bwd_kernel(*refs, norms, d, eps):
    n = len(norms)
    x_refs, h_refs, w_refs, g_refs, dx_refs, dw_refs = (
        refs[i * n:(i + 1) * n] for i in range(6))
    xs_ref, dys_ref, next_ref = refs[6 * n:]
    rows, width = x_refs[0].shape[1:]
    step = pl.program_id(2)                 # the row blocks, last first
    first = step == pl.num_programs(2) - 1  # the sequence's first block

    @pl.when((pl.program_id(1) == 0) & (step == 0))
    def _init():
        for dw_ref in dw_refs:
            dw_ref[...] = jnp.zeros_like(dw_ref)

    for i, (x_ref, h_ref, w_ref, g_ref, dx_ref, dw_ref, norm) in enumerate(
            zip(x_refs, h_refs, w_refs, g_refs, dx_refs, dw_refs, norms)):
        _history(x_ref, h_ref, xs_ref, first)
        # dy of the rows after the block: the block done before this one
        dys_ref[rows:] = jnp.where(step == 0, 0.0, next_ref[i])
        for c0 in range(0, width, d):       # a head at a time
            cols = slice(c0, c0 + d)
            w_rows = _tap_rows(w_ref, cols)
            taps = len(w_rows)
            xs = _shifted(xs_ref, cols, rows, taps)
            y = sum(x * w for x, w in zip(xs, w_rows))
            a, sg = _silu(y)
            da = g_ref[0, :, cols].astype(_F32)
            if norm:
                inv = jax.lax.rsqrt(
                    jnp.sum(a * a, axis=-1, keepdims=True) + eps)
                dot = jnp.sum(da * a, axis=-1, keepdims=True)
                da = inv * (da - a * (inv * inv * dot))
            dy = da * (sg + a * (1.0 - sg))
            dys_ref[0:rows, cols] = dy
            for j, x in enumerate(xs):
                dw_ref[j:j + 1, cols] += jnp.sum(dy * x, axis=0,
                                                 keepdims=True)
            dx = sum(g * w for g, w in zip(
                _shifted(dys_ref, cols, rows, taps, after=True), w_rows))
            dx_ref[0, :, cols] = dx.astype(dx_ref.dtype)
        next_ref[i] = dys_ref[0:_HALO]


def _blocks(shape, d):
    """(rows, columns) of a block and the specs of a projection's block,
    its halo and its taps over the grid (column tile, batch, row block);
    ``order(i)`` is the row block a grid step works on."""
    _, s, width = shape
    rows = _largest(_ROWS, _HALO_ROWS, s)
    cols = _largest(max(_COLS, d), d, width)
    per = rows // _HALO_ROWS

    def specs(order, n_taps):
        block = pl.BlockSpec((1, rows, cols),
                             lambda j, b, i: (b, order(i), j))
        halo = pl.BlockSpec(
            (1, _HALO_ROWS, cols),
            lambda j, b, i: (b, jnp.maximum(order(i) * per - 1, 0), j))
        taps = pl.BlockSpec((n_taps, cols), lambda j, b, i: (0, j))
        return block, halo, taps

    return rows, cols, specs


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _pallas_fwd(projections, taps, norms, d, eps):
    n, x = len(projections), projections[0]
    b, s, width = x.shape
    rows, cols, specs = _blocks(x.shape, d)
    block, halo, tap = specs(lambda i: i, taps[0].shape[0])
    return tuple(pl.pallas_call(
        functools.partial(_fwd_kernel, norms=norms, d=d, eps=eps),
        name="kda_prep",
        grid=(width // cols, b, s // rows),
        in_specs=[block] * n + [halo] * n + [tap] * n,
        out_specs=[block] * n,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype)
                   for p in projections],
        scratch_shapes=[pltpu.VMEM((_HALO + rows, cols), _F32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=_interpret(),
    )(*projections, *projections, *taps))


def _pallas_bwd(projections, taps, norms, d, eps, cotangents):
    n, x = len(projections), projections[0]
    b, s, width = x.shape
    rows, cols, specs = _blocks(x.shape, d)
    last = s // rows - 1
    block, halo, tap = specs(lambda i: last - i, taps[0].shape[0])
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, norms=norms, d=d, eps=eps),
        name="kda_prep_bwd",
        grid=(width // cols, b, s // rows),
        in_specs=[block] * n + [halo] * n + [tap] * n + [block] * n,
        out_specs=[block] * n + [tap] * n,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype)
                   for p in projections]
        + [jax.ShapeDtypeStruct(w.shape, _F32) for w in taps],
        scratch_shapes=[pltpu.VMEM((_HALO + rows, cols), _F32),
                        pltpu.VMEM((rows + _HALO, cols), _F32),
                        pltpu.VMEM((n, _HALO, cols), _F32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=_interpret(),
    )(*projections, *projections, *taps,
      *(g.astype(p.dtype) for g, p in zip(cotangents, projections)))
    return tuple(outs[:n]), tuple(
        dw.astype(w.dtype) for dw, w in zip(outs[n:], taps))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def pallas_kda_prep(projections, taps, norms, head_dim, eps):
    """The kernels themselves, whatever the platform (interpreted on the
    CPU): tuples of projections ``[b, s, heads * d]`` and of their tap
    weights, ``norms`` a flag a branch. What tests/test_solar_open2.py
    compares with ``xla_kda_prep``."""
    return _pallas_fwd(projections, taps, norms, head_dim, eps)


pallas_kda_prep.defvjp(
    lambda projections, taps, norms, head_dim, eps: (
        _pallas_fwd(projections, taps, norms, head_dim, eps),
        (projections, taps)),
    lambda norms, head_dim, eps, res, cotangents: _pallas_bwd(
        *res, norms, head_dim, eps, cotangents))


# ---------------------------------------------------------------------------
# the entry
# ---------------------------------------------------------------------------
def prep_path(seq: int, head_dim: int, taps: int) -> str:
    """``"pallas"`` or ``"xla"`` for a chain of these sizes traced here:
    the kernels need the TPU as the target, no multi-device auto mesh open
    at the trace, rows that fill the halo's tile, heads that fill the lanes
    and a history the halo holds."""
    from ..core.place import target_platform
    from ..distributed import context as dctx

    if (target_platform() == "tpu" and dctx.kernel_auto_axes() is None
            and seq % _HALO_ROWS == 0 and head_dim % 128 == 0
            and taps - 1 <= _HALO):
        return "pallas"
    return "xla"


def kda_prep(projections, taps, norms, head_dim, eps):
    """The scan's operands from the raw projections: tuples of
    ``[b, s, heads * head_dim]`` arrays and of their ``[taps, heads *
    head_dim]`` tap weights, ``norms[i]`` whether branch ``i`` ends in the
    ``l2norm`` a head. Differentiable towards both tuples."""
    from ..profiler import metrics

    projections, taps = tuple(projections), tuple(taps)
    norms = tuple(bool(f) for f in norms)
    path = prep_path(projections[0].shape[1], head_dim, taps[0].shape[0])
    metrics.registry().counter("kda/prep_calls{path=%s}" % path).add(1)
    if path == "pallas":
        return pallas_kda_prep(projections, taps, norms, int(head_dim),
                               float(eps))
    return xla_kda_prep(projections, taps, norms, head_dim, eps)
