"""Ragged paged attention over a page-table KV cache.

Serving keeps the KV cache as a fixed pool of fixed-size pages
(``paddle_tpu.serving.paged_cache``) instead of one dense
``[N, S_max, NH, D]`` slab per request batch: a request holds only the
pages its sequence actually fills, so HBM scales with live tokens, not
with ``S_max × slots``. This module is the attention read side of that
layout, unified the way "Ragged Paged Attention" (PAPERS.md) argues a
TPU serving kernel should be: ONE entry point,
``ragged_paged_attention``, over per-row metadata ``(page_table row,
pos0, true_len)`` — a decode step is simply a row with
``true_len == 1``, a prefill chunk is a row with ``true_len`` up to its
chunk width, and both kinds share one program, one grid, one softmax
spelling. The engine's mixed prefill/decode tick flattens every token
in flight into rows of this one call (``models/gpt.py::
gpt_ragged_apply``, through ``serving.paged_cache.Pools.attend``).

Two implementations behind the one entry point, following the
``ops/int8_matmul.py`` precedent (kernel built and gated; the XLA
spelling is the measured default until the kernel wins on hardware):

- ``impl="xla"`` (default): gather each row's pages into a contiguous
  ``[R, S_cap, NH, D]`` view and run exactly the dense-cache attention
  expression from ``models/gpt.py::gpt_cached_apply`` — same einsum
  contractions, same mask constant, same f32 softmax — via the ONE
  shared helper ``_gather_attend`` (decode rows, chunk rows and
  verify rows all route here, so "same expression" is enforced by
  code, not by a verbatim-copy comment). This is what makes greedy
  paged decode **bitwise** equal to the dense ``generate`` path
  (tests/test_serving.py): XLA fuses the gather into the attention so
  the page indirection costs index arithmetic, not a second cache.
- ``impl="pallas"``: the ragged Pallas kernel — grid
  ``(rows, pages_per_slot)``, page table / pos0 / true_len
  scalar-prefetched so each grid step DMAs one page directly from the
  pool (no materialized gather), online-softmax accumulation in VMEM
  scratch across the page axis, and **fully-masked page blocks
  skipped**: a block whose first position exceeds the row's last
  attendable position (``pos0 + true_len - 1``) contributes nothing,
  so its compute is predicated off and its DMA is routed to the null
  page by the index map (the grid still visits the step — the win is
  skipped FLOPs + a cached null-page fetch, stated honestly). Gated
  behind the same TPU guard as ``ops/flash_attention.py`` (interpret
  mode on CPU). Numerics are allclose, not bitwise, vs the XLA path
  (online softmax reassociates the reduction), so the serving engine
  only selects it on explicit request. On a v5e it compiles and agrees
  with the XLA spelling for bf16 and int8 pools (chip_smoke.py phase
  1); a default flip waits for a speed measurement (ROADMAP S6).

Layout note: pools are ``[num_pages, page_size, NH, D]`` per layer;
page 0 is the null page (writes of inactive rows land there, gathers
of unallocated table entries read it and are masked). Every entry point
also takes the pools STACKED over layers, ``[L, num_pages, page_size,
NH, D]`` (scales ``[L, P, NH]``), with ``layer=`` an index that may be
traced: the layer then rides inside the scatter's and the gather's own
indices (``pool.at[layer, page, off]``, ``pool[layer, page_table]``), so
no operation's result is a layer of the pool and a ``lax.scan`` that
carries the stack updates it in place (ROADMAP S3;
``serving.paged_cache.Pools``). The arithmetic after the gather is the
same statements either way.

Quantized pools (ISSUE 12): with ``kv_dtype="int8"`` the pools store
int8 values plus per-page **per-head** f32 scales ``[P, NH]`` per
layer (one outlier head costs one head's precision, not the page's —
the per-channel idiom of ``ops/int8_matmul.py``). The write side is
``paged_kv_scatter``: each token's per-head amax scatter-MAXes into
its page's scale, resident page content is re-quantized when the
scale grows (``round(q·s_old/s_new)`` — an exact no-op while the
scale is unchanged, which is the steady state), and the new token is
quantized at the final scale; the null page's scale contribution is
masked so it stays 0 forever. The read side dequantizes inside
``_gather_attend`` for the XLA spelling, and in VMEM for the Pallas
kernel (which DMAs each page's scale rows by the page's own index map
and dequantizes before the online softmax). The f32 path is
bit-for-bit untouched (no cast, no extra ops) — the engine's bitwise
parity contract only ever applied to unquantized pools, and still
does.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_paged_attention", "paged_kv_scatter"]

_NEG_INF = -1e9     # same masking constant as gpt_cached_apply


def _interpret() -> bool:
    from ..core.place import target_platform

    return target_platform() == "cpu"


def _at(layer, *index):
    """``index`` into one layer's pool, or ``(layer,) + index`` into the
    stack: the one place that says where the layer axis is."""
    return index if layer is None else (layer,) + index


def _gather_attend(q, k_pool, v_pool, page_table, qpos,
                   k_scale=None, v_scale=None, layer=None):
    """THE dense paged-attention expression — the single spelling of
    gather + mask + f32 softmax behind ``impl="xla"`` (and,
    transitively, the spelling ``gpt_cached_apply`` uses on the dense
    cache: same contraction order, same mask constant,
    same softmax dtype — which is what the engine's bitwise greedy
    parity contract rests on).

    q           [R, T, NH, D]  queries
    k_pool      [P, ps, NH, D] per-layer key page pool
    v_pool      [P, ps, NH, D] per-layer value page pool
    page_table  [R, NPs] int32 page ids per row (0 = null page)
    qpos        [R, T] int32   last attendable cache position per query
    k_scale     [P, NH] f32    per-page per-head dequant scales (int8
    v_scale     [P, NH]        pools only; None leaves the math — and
                               the f32 parity contract — untouched)
    layer       int32 scalar   None: the pools are one layer's; else
                               they are stacked ``[L, ...]`` and the
                               layer is one more index of the gathers

    Every reduction runs at the full slot capacity ``NPs * ps`` with
    exact-zero weights behind the mask, so results are independent of
    page layout and of whatever garbage sits in unattended positions.
    Quantized pools dequantize right after the gather (value ·
    per-page per-head scale), so everything downstream — contraction
    order, mask constant, softmax dtype — is the one shared spelling
    regardless of storage dtype. Returns [R, T, NH, D].
    """
    r = q.shape[0]
    ps, nh, hd = k_pool.shape[-3:]
    nps = page_table.shape[1]
    s_cap = nps * ps
    at = _at(layer, page_table)
    k_c = k_pool[at]                        # [R, NPs, ps, NH, D]
    v_c = v_pool[at]
    if k_scale is not None:
        # int8 pools: dequant with the gathered per-page per-head
        # scales (null pages carry scale 0, so their garbage reads as
        # exact zeros even before the mask)
        k_c = k_c.astype(q.dtype) * k_scale[at][:, :, None, :, None]
        v_c = v_c.astype(q.dtype) * v_scale[at][:, :, None, :, None]
    elif k_pool.dtype != q.dtype:
        # mixed storage/compute dtypes: contract at the WIDER of the
        # two — upcasting a bf16 pool under an f32 model is free, and
        # DOWNcasting an f32 pool under a bf16 model would throw away
        # exactly the precision kv_dtype='f32' paid double the HBM for
        wide = jnp.promote_types(k_pool.dtype, q.dtype)
        k_c = k_c.astype(wide)
        v_c = v_c.astype(wide)
    k_c = k_c.reshape(r, s_cap, nh, hd)
    v_c = v_c.reshape(r, s_cap, nh, hd)
    key_pos = jnp.arange(s_cap)
    mask = key_pos[None, None, None, :] <= qpos[:, None, :, None]
    att = jnp.einsum("btnd,bsnd->bnts", q, k_c) / math.sqrt(hd)
    att = jnp.where(mask, att, _NEG_INF)
    w = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bnts,bsnd->btnd", w, v_c)
    # mixed-dtype contraction may promote; hand back the query dtype
    # (identity — same array object — on the homogeneous f32 path, so
    # the bitwise parity contract is untouched)
    return out if out.dtype == q.dtype else out.astype(q.dtype)


def ragged_paged_attention(q, k_pool, v_pool, page_table, pos0, true_len,
                           impl: str = "xla", k_scale=None,
                           v_scale=None, layer=None):
    """One attention call over ragged rows of the page pool.

    q           [R, T, NH, D]  per-row query blocks (T static)
    k_pool      [P, ps, NH, D] per-layer key page pool
    v_pool      [P, ps, NH, D] per-layer value page pool
    page_table  [R, NPs] int32 page ids per row (0 = null page)
    pos0        [R] int32      absolute position of each row's query 0
    true_len    [R] int32      real queries in the row (1 = decode row)
    k_scale     [P, NH] f32    dequant scales for int8 pools (both
    v_scale     [P, NH]        impls; None = unquantized pools)
    layer       int32 scalar   with it the pools (and scales) are the
                               stacks ``[L, ...]`` and this layer of
                               them is read, by index (both impls)

    Query ``i`` of row ``r`` attends cache positions
    ``<= pos0[r] + i``. Rows are fixed-shape: queries at
    ``i >= true_len[r]`` are computed anyway and produce garbage the
    caller must ignore (on the Pallas path their trailing page blocks
    are additionally skipped, so the garbage differs between impls —
    never compare pad queries). Returns [R, T, NH, D].
    """
    if impl == "xla":
        t = q.shape[1]
        qpos = pos0[:, None] + jnp.arange(t, dtype=pos0.dtype)[None, :]
        return _gather_attend(q, k_pool, v_pool, page_table, qpos,
                              k_scale=k_scale, v_scale=v_scale,
                              layer=layer)
    if impl == "pallas":
        return _ragged_attention_pallas(q, k_pool, v_pool, page_table,
                                        pos0, true_len,
                                        k_scale=k_scale, v_scale=v_scale,
                                        layer=layer)
    raise ValueError(f"unknown paged attention impl {impl!r}")


def paged_kv_scatter(pool, scale, page, off, vals, layer=None):
    """Write one tick's per-token KV into the page pool — the single
    write-side spelling shared by the unified tick and the spec verify
    tick (via ``gpt_ragged_apply``).

    pool   [P, ps, NH, D]  per-layer page pool (f32/bf16/int8)
    scale  [P, NH] f32     per-page per-head scales (int8 pools; None
                           otherwise)
    page   [NT] int32      target page per token (0 = null page)
    off    [NT] int32      offset within the page
    vals   [NT, NH, D]     the token KV (model dtype)
    layer  int32 scalar    None: ``pool``/``scale`` are one layer's;
                           else they are the stacks ``[L, ...]`` and
                           every index below gains the layer, so the
                           stack is written in place and returned

    Unquantized pools: one scatter (cast to the pool dtype). int8
    pools quantize-on-write with RUNNING per-page scales:

    1. each token's per-head ``amax/127`` scatter-maxes into its
       page's scale row (null-page contributions masked to 0, so the
       null page's scale stays 0 — its garbage dequantizes to exact
       zeros);
    2. pages whose scale grew have their resident int8 content
       re-quantized ``round(q · s_old/s_new)`` — an exact no-op
       (``round(q·1) == q``) whenever the scale is unchanged, which is
       every steady-state decode write; a freshly-reset page
       (``s_old == 0``) is zeroed, which also sanitizes recycled-page
       garbage;
    3. the token is quantized at the final scale (``|q| <= 127`` by
       construction: the page scale is >= the token's own amax/127).

    The rescale pass gathers + rewrites one page per token per layer —
    the documented write-amplification cost of keeping ONE scale per
    page (bounded by ``page_size`` rows per token; decode ticks touch
    one page per slot). Duplicate page targets (a prefill chunk
    landing several tokens in one page) are safe: every duplicate
    computes the same rescaled page from the same pre-write content,
    and the offset writes are disjoint.

    Returns (pool, scale) — scale is None when it came in None.
    """
    if scale is None:
        vals = vals if vals.dtype == pool.dtype \
            else vals.astype(pool.dtype)
        return pool.at[_at(layer, page, off)].set(vals), None
    pages = _at(layer, page)
    a = jnp.max(jnp.abs(vals.astype(jnp.float32)), axis=-1) / 127.0
    a = jnp.where((page > 0)[:, None], a, 0.0)          # [NT, NH]
    s_old = scale[pages]                                # [NT, NH]
    scale = scale.at[pages].max(a)
    s_new = scale[pages]
    ratio = jnp.where(s_new > 0.0,
                      s_old / jnp.maximum(s_new, 1e-30), 0.0)
    pg = pool[pages].astype(jnp.float32)                # [NT, ps, NH, D]
    pg = jnp.round(pg * ratio[:, None, :, None])
    pool = pool.at[pages].set(pg.astype(jnp.int8))
    q = jnp.round(vals.astype(jnp.float32)
                  / jnp.maximum(s_new, 1e-30)[:, :, None])
    q = jnp.clip(q, -127.0, 127.0)
    pool = pool.at[_at(layer, page, off)].set(q.astype(jnp.int8))
    return pool, scale


# --------------------------------------------------------------------------
# Pallas ragged kernel
# --------------------------------------------------------------------------

def _ragged_kernel(pt_ref, pos0_ref, tl_ref, layer_ref, q_ref, k_ref, v_ref,
                   *rest, page_size: int, n_pages: int):
    """Grid (r, j): row r consumes its j-th page. Page table, pos0,
    true_len and the layer are scalar-prefetched, so the BlockSpec index
    map DMAs page ``pt[r, j]`` of layer ``layer[0]`` straight from the
    stacked pool (the layer axis is squeezed out of the block: the body
    sees one page) — the gathered
    [R, S_cap] intermediate of the XLA path never exists — and routes
    fully-masked blocks (``j*ps > pos0 + true_len - 1``, where nothing
    in the page is attendable by any real query of the row) to the
    null page with their compute predicated off. Running max /
    denominator / accumulator live in VMEM scratch across the page
    axis (online softmax). Quantized pools add two inputs — the
    per-page per-head scale rows, DMA'd by the SAME index map as the
    page itself — and dequantize in VMEM right after the (int8) page
    loads, before anything touches the MXU."""
    if len(rest) == 6:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    r = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    last_attendable = pos0_ref[r] + tl_ref[r] - 1

    @pl.when(j * page_size <= last_attendable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                # [T, NH, D]
        k = k_ref[0].astype(jnp.float32)                # [ps, NH, D]
        v = v_ref[0].astype(jnp.float32)
        if ks_ref is not None:
            # in-VMEM dequant: page values × this page's [NH, 1] scales
            k = k * ks_ref[0][None]
            v = v * vs_ref[0][None]
        hd = q.shape[-1]
        # s[n, t, p] = q[t, n] · k[p, n] / sqrt(D)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32) / math.sqrt(hd)
        # query t attends global position <= pos0 + t
        gpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        qpos = pos0_ref[r] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(gpos <= qpos, s, _NEG_INF)
        m_prev = m_ref[:]                               # [NH, T, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)                          # [NH, T, ps]
        corr = jnp.exp(m_prev - m_new)                  # [NH, T, 1]
        l_ref[:] = corr * l_ref[:] + jnp.sum(p, axis=2, keepdims=True)
        # acc[n, t, d] += sum_p p[n, t, p] * v[p, n, d]
        pv = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)         # [NH, T, D]
        acc_ref[:] = corr * acc_ref[:] + pv
        m_ref[:] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        # rows whose every block was skipped (degenerate metadata) get
        # zeros, not 0/0 NaN — they are never read, but NaN would trip
        # debug_nans and pollute allclose diagnostics
        l_safe = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0] = jnp.transpose(acc_ref[:] / l_safe,
                                 (1, 0, 2)).astype(o_ref.dtype)


def _ragged_attention_pallas(q, k_pool, v_pool, page_table, pos0,
                             true_len, k_scale=None, v_scale=None,
                             layer=None):
    r, t, nh, hd = q.shape
    ps = k_pool.shape[-3]
    nps = page_table.shape[1]
    if layer is None:
        # one layer's pools are a stack of one (a reshape): the kernel
        # has one spelling, the stacked one
        layer = 0
        k_pool, v_pool = k_pool[None], v_pool[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def _page(i, j, pt, p0, tl):
        # fully-masked block: fetch the (hot, tiny) null page instead
        # of a live pool page the row will only mask away
        return jnp.where(j * ps <= p0[i] + tl[i] - 1, pt[i, j], 0)

    def _kv_index(i, j, pt, p0, tl, ly):
        return (ly[0], _page(i, j, pt, p0, tl), 0, 0, 0)

    def _scale_index(i, j, pt, p0, tl, ly):
        # the scale row rides the same page choice as the page itself
        return (ly[0], _page(i, j, pt, p0, tl), 0, 0)

    in_specs = [
        pl.BlockSpec((1, t, nh, hd),
                     lambda i, j, pt, p0, tl, ly: (i, 0, 0, 0)),
        pl.BlockSpec((None, 1, ps, nh, hd), _kv_index),
        pl.BlockSpec((None, 1, ps, nh, hd), _kv_index),
    ]
    args = (page_table, pos0, true_len, layer, q, k_pool, v_pool)
    if k_scale is not None:
        # scales enter as [L, P, NH, 1]: a (1, NH) block of the [P, NH]
        # rows breaks Mosaic's rule that a block's last two dims be
        # (8, 128)-divisible or the array's own, and [NH, 1] is already
        # the page tile's layout (heads on sublanes, broadcast along
        # the head_dim lanes)
        in_specs += [pl.BlockSpec((None, 1, nh, 1), _scale_index),
                     pl.BlockSpec((None, 1, nh, 1), _scale_index)]
        args += (k_scale[..., None], v_scale[..., None])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r, nps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, t, nh, hd),
                               lambda i, j, pt, p0, tl, ly: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, t, 1), jnp.float32),
            pltpu.VMEM((nh, t, 1), jnp.float32),
            pltpu.VMEM((nh, t, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, page_size=ps, n_pages=nps),
        name="ragged_paged_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, t, nh, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(*args)
