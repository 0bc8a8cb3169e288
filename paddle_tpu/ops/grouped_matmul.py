"""Grouped matrix multiplication over ragged row groups (Pallas TPU).

``grouped_matmul(lhs [M, K], rhs [E, K, N], group_sizes [E]) -> [M, N]``:
rows ``group_sizes[:e].sum() : group_sizes[:e+1].sum()`` of ``lhs`` are
multiplied by ``rhs[e]``. The sizes sum to ``M``; a group may be empty.
This is ``jax.lax.ragged_dot``'s contract, and ``ragged_dot`` is one of the
two paths: XLA:TPU's own kernel for it ran the drop-less experts at a third
of the chip's peak (PERF.md section 6, PR 26), so on one TPU device the
products run through the kernels below instead, whose Mosaic calls are
named ``moe_gmm`` (rows times a group's matrix, either orientation of the
right side) and ``moe_tgmm`` (a group's rows transposed times its rows of
another matrix: the gradient towards the weights). The tiling scheme is
the one of jax's ``pallas.ops.tpu.megablox``: row tiles are visited group
by group, a tile that two groups share once by each, and scalar-prefetched
tables say which group and which tile a grid step works on. Here the
tables are three gathers, the kernels mask only the tiles a group's edge
cuts, and a grid step holds a group's matrix over the whole contraction
(``tile_for``): the rows' block then stays in VMEM from visit to visit and
a visit fetches ``[k, tn]`` weights in one copy, which is what a served
tick of one or two rows an expert is made of.

Which path a call takes is decided by what the trace can observe
(``kernel_path``): the platform compiled for, whether a multi-device auto
mesh is open (XLA does not partition a Mosaic call; it does partition
``ragged_dot``, and the stacked experts are declared over ``ep``), and
whether the shapes tile. There is no option.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

__all__ = ["grouped_matmul", "kernel_path", "pallas_grouped_matmul",
           "tile_for"]

# the kernels keep whole weight tiles resident: more than Mosaic's default
# 16 MiB of scoped VMEM, well inside the v5e's 128 MiB
_VMEM_LIMIT = 64 * 1024 * 1024
# the weights of a group's matrix that one grid step holds, at most: 4 MB of
# bf16 a buffer. Two buffers of it, two of the rows' block [128, k], the
# output block and its float32 result fit ``_VMEM_LIMIT`` many times over;
# ``moe_tgmm`` holds a tile as two output buffers and a float32 sum, 16 MB
_TILE_WEIGHTS = 2 ** 21


def _divisors(x: int):
    """The multiples of 128 that divide ``x`` (one itself), largest first."""
    return [d for d in range(x, 0, -128) if x % d == 0]


def tile_for(m: int, k: int, n: int) -> Optional[Tuple[int, int, int]]:
    """(tm, tk, tn) for a product of ``m`` rows, contraction ``k`` and
    ``n`` columns, or None where the shape does not tile (a side that is
    no multiple of 128). A function of the three sides and nothing else.

    128 rows, because a tile that a group's edge cuts is computed once for
    each group it holds and 64 groups cut up to 63 tiles (found on the v5e
    for OLMoE's products, PERF.md section 6, PR 27). **The whole
    contraction in one step** (``tk == k``: no accumulator, and the rows'
    block index does not turn with the steps, so the rows are fetched once
    a row tile and not once a step) with ``tn`` the largest divisor of
    ``n`` whose ``[k, tn]`` weights are within ``_TILE_WEIGHTS``; only a
    contraction of which not even 128 columns fit is cut, at its largest
    divisor that leaves 128. The sides' divisors are all multiples of 128,
    not powers of two: an expert of ``[2560, 768]`` (Ling-3.0-flash) is one
    step a visit where the powers of two made fifteen of 256 KB, each as
    long in its fixed cost as in its copy (PERF.md section 6, PR 50;
    ``benchmarks/grouped_matmul_bench.py`` is where the budget was read).
    OLMoE's ``[2048, 1024]`` is the whole matrix, as it was."""
    if min(m, k, n) <= 0 or m % 128 or k % 128 or n % 128:
        return None
    tk = next(d for d in _divisors(k) if d * 128 <= _TILE_WEIGHTS)
    tn = next(d for d in _divisors(n) if tk * d <= _TILE_WEIGHTS)
    return 128, tk, tn


def kernel_path(m: int, k: int, n: int) -> str:
    """``"pallas"`` or ``"xla"`` for a product of these sizes traced here:
    the kernel needs the TPU as the target, no multi-device auto mesh
    open at the trace, and sizes that tile."""
    from ..core.place import target_platform
    from ..distributed import context as dctx

    if (target_platform() == "tpu" and dctx.kernel_auto_axes() is None
            and tile_for(m, k, n) is not None):
        return "pallas"
    return "xla"


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs [M, K] x rhs [E, K, N] over ``group_sizes`` [E] -> [M, N] in
    ``lhs``'s dtype, accumulated in float32; differentiable towards
    ``lhs`` and ``rhs``. See the module's text for the two paths."""
    m, k = lhs.shape
    if kernel_path(m, k, rhs.shape[2]) == "pallas":
        return pallas_grouped_matmul(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


# ---------------------------------------------------------------------------
# which group and which row tile a grid step works on
# ---------------------------------------------------------------------------
# jitted: a step calls it a dozen times and traces it twice, which is a
# second of every set-up on the chip's host (PERF.md section 6, PR 27)
@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _visits(group_sizes, m: int, tm: int, visit_empty: bool):
    """The row tiles in the order the kernels visit them: group by group,
    each group the tiles its rows touch. Returns ``(offsets [E+1],
    group_of_visit [V], tile_of_visit [V], n_visits)`` with ``V = m // tm
    + E - 1`` the most there can be; entries past ``n_visits`` are never
    read. An empty group has no visit, or one (``visit_empty``: its
    block of the weights' gradient still has to be written, as zeros)."""
    e = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes == 0, int(visit_empty),
                      (ends + tm - 1) // tm - first)
    visit0 = jnp.cumsum(tiles) - tiles          # a group's first visit
    v = m // tm + e - 1
    group_of = jnp.repeat(jnp.arange(e, dtype=jnp.int32), tiles,
                          total_repeat_length=v)
    tile_of = first[group_of] + jnp.arange(v, dtype=jnp.int32) \
        - visit0[group_of]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, group_of, jnp.clip(tile_of, 0, m // tm - 1),
            jnp.sum(tiles))


def _rows_in_group(offsets_ref, group, tile, tm: int):
    """(whole, mask): whether the group holds every row of the tile, and
    a function giving the [tm, width] mask of the rows it holds (built
    only in the branch that needs it)."""
    lo, hi = offsets_ref[group], offsets_ref[group + 1]
    row0 = tile * tm

    def mask(width):
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0)
        return (rows >= lo) & (rows < hi)

    return (lo <= row0) & (row0 + tm <= hi), mask


# ---------------------------------------------------------------------------
# rows x a group's matrix
# ---------------------------------------------------------------------------
def _gmm_kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
                *acc, tm, tn, tiles_k, transpose_rhs):
    v, ki = pl.program_id(1), pl.program_id(2)
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
    part = jax.lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                               preferred_element_type=jnp.float32)

    def store(result):
        whole, mask = _rows_in_group(offsets_ref, group_ref[v], tile_ref[v],
                                     tm)

        @pl.when(whole)
        def _():
            out_ref[...] = result.astype(out_ref.dtype)

        # a tile that groups share is visited by each in turn and keeps
        # the other groups' rows
        @pl.when(jnp.logical_not(whole))
        def _():
            out_ref[...] = jnp.where(
                mask(tn), result, out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    if tiles_k == 1:
        store(part)
        return
    acc_ref, = acc

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = part

    @pl.when(ki > 0)
    def _():
        acc_ref[...] += part

    @pl.when(ki == tiles_k - 1)
    def _():
        store(acc_ref[...])


def _gmm(lhs, rhs, group_sizes, tile, transpose_rhs: bool):
    """lhs [m, k] x rhs [E, k, n] (``transpose_rhs``: [E, n, k]) -> [m, n]."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tile
    tiles_k, tiles_n = k // tk, n // tn
    offsets, group_of, tile_of, n_visits = _visits(group_sizes, m, tm, False)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (None, tn, tk), lambda ni, v, ki, o, g, t: (g[v], ni, ki))
    else:
        rhs_spec = pl.BlockSpec(
            (None, tk, tn), lambda ni, v, ki, o, g, t: (g[v], ki, ni))
    size = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn, tiles_k=tiles_k,
                          transpose_rhs=transpose_rhs),
        name="moe_gmm",
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, v, ki, o, g, t: (t[v], ki)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda ni, v, ki, o, g, t: (t[v], ni)),
            grid=(tiles_n, n_visits, tiles_k),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if tiles_k > 1 else []),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=size * (m * k * tiles_n + rhs.size + m * n)),
        interpret=_interpret(),
    )(offsets, group_of, tile_of, lhs, rhs)


# ---------------------------------------------------------------------------
# a group's rows, transposed, x its rows of another matrix
# ---------------------------------------------------------------------------
def _tgmm_kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
                 acc_ref, *, tm, tk, tn):
    v, end = pl.program_id(2), pl.num_programs(2) - 1
    group = group_ref[v]
    # v - 1 and v + 1 are read only where they exist
    first = jnp.logical_or(v == 0, group_ref[jnp.maximum(v - 1, 0)] != group)
    last = jnp.logical_or(v == end,
                          group_ref[jnp.minimum(v + 1, end)] != group)
    dims = (((0,), (0,)), ((), ()))

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    whole, mask = _rows_in_group(offsets_ref, group, tile_ref[v], tm)

    @pl.when(whole)
    def _():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims,
            preferred_element_type=jnp.float32)

    # the rows of other groups count as zeros (in the narrower operand,
    # the cheaper one to mask); for an empty group's one visit that is
    # every row
    @pl.when(jnp.logical_not(whole))
    def _():
        lhs, rhs = lhs_ref[...], rhs_ref[...]
        if tk <= tn:
            lhs = jnp.where(mask(tk), lhs, jnp.zeros_like(lhs))
        else:
            rhs = jnp.where(mask(tn), rhs, jnp.zeros_like(rhs))
        acc_ref[...] += jax.lax.dot_general(
            lhs, rhs, dims, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm(lhs, rhs, group_sizes, tile):
    """lhs [m, k], rhs [m, n] -> [E, k, n]: ``lhs[rows of e].T @ rhs[rows
    of e]`` for every group, zeros for an empty one."""
    m, k = lhs.shape
    n = rhs.shape[1]
    e = group_sizes.shape[0]
    tm, tk, tn = tile
    tiles_k, tiles_n = k // tk, n // tn
    offsets, group_of, tile_of, n_visits = _visits(group_sizes, m, tm, True)
    size = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, tk=tk, tn=tn),
        name="moe_tgmm",
        out_shape=jax.ShapeDtypeStruct((e, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, ki, v, o, g, t: (t[v], ki)),
                pl.BlockSpec((tm, tn), lambda ni, ki, v, o, g, t: (t[v], ni)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda ni, ki, v, o, g, t: (g[v], ki, ni)),
            grid=(tiles_n, tiles_k, n_visits),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=size * (m * k * tiles_n + m * n * tiles_k
                                   + e * k * n)),
        interpret=_interpret(),
    )(offsets, group_of, tile_of, lhs, rhs)


# ---------------------------------------------------------------------------
# the product and its gradients
# ---------------------------------------------------------------------------
def _tile_or_raise(m: int, k: int, n: int):
    tile = tile_for(m, k, n)
    if tile is None:
        raise ValueError(
            f"grouped matmul of {m} rows, contraction {k}, {n} columns does "
            "not tile (multiples of 128 are needed); grouped_matmul() "
            "falls back to jax.lax.ragged_dot for such shapes")
    return tile


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def pallas_grouped_matmul(lhs, rhs, group_sizes, transpose_rhs=False):
    """The kernels' path of ``grouped_matmul``, whatever the platform (on
    the CPU Pallas interprets them). ``transpose_rhs``: rhs is [E, N, K]."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return _gmm(lhs, rhs, group_sizes, _tile_or_raise(m, k, n),
                transpose_rhs)


def _pgm_fwd(lhs, rhs, group_sizes, transpose_rhs):
    return (pallas_grouped_matmul(lhs, rhs, group_sizes, transpose_rhs),
            (lhs, rhs, group_sizes))


def _pgm_bwd(transpose_rhs, res, g):
    lhs, rhs, group_sizes = res
    m, k = lhs.shape
    n = g.shape[1]
    d_lhs = _gmm(g, rhs, group_sizes, _tile_or_raise(m, n, k),
                 not transpose_rhs)
    # the gradient in rhs's own orientation
    a, b = (g, lhs) if transpose_rhs else (lhs, g)
    d_rhs = _tgmm(a, b, group_sizes,
                  _tile_or_raise(m, a.shape[1], b.shape[1]))
    return d_lhs, d_rhs, None


pallas_grouped_matmul.defvjp(_pgm_fwd, _pgm_bwd)
