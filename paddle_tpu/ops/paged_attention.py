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

Two implementations behind the one entry point, picked where the
program is traced (``resolve_impl``: ``core.place.target_platform()``,
as ``ops/flash_attention.py`` picks Mosaic or interpret mode), unless
the caller names one:

- ``impl="xla"`` (the reference, and what anything but a TPU runs):
  gather each row's pages into a contiguous ``[R, S_cap, NH, D]`` view
  and run exactly the dense-cache attention expression from
  ``models/gpt.py::gpt_cached_apply`` — same einsum contractions, same
  mask constant, same f32 softmax — via the ONE shared helper
  ``_gather_attend`` (decode rows, chunk rows and verify rows all route
  here, so "same expression" is enforced by code, not by a
  verbatim-copy comment). This is what makes greedy paged decode
  **bitwise** equal to the dense ``generate`` path
  (tests/test_serving.py, which run on the CPU). Its cost is the slot's
  capacity whatever is live: on a v5e 14.3 ms for the 24 layers of 12
  decode rows of 2,048 (PERF.md section 6, PR 34).
- ``impl="pallas"`` (the chip's serving kernel, ISSUE 34): the ragged
  kernel ``ragged_paged_attn`` — grid ``(rows,)``; the pools stay in
  HBM, stacked, and the KV axis is a loop INSIDE the kernel over blocks
  of ``_BLOCK_TOKENS`` positions whose trip count is the row's own
  ``ceil((pos0 + true_len) / block)``, read from the scalar-prefetched
  metadata. A block's live pages are fetched by page id with the
  kernel's own asynchronous copies into one of two VMEM buffers, the
  next block (or the next row's first) in flight while this one is
  multiplied, so **a page past a row's last live position is never
  read** (tests/test_ragged_kernel.py fills them with NaN) and a row of
  length 0 costs a grid step and no copy. bf16 pages meet the MXU as
  bf16, float32 accumulated, under a float32 online softmax; int8 pages
  are widened in VMEM and their scale rows applied to the block's
  scores and weights. On a v5e it reads a long row's live K and V at
  690-740 GB/s (PERF.md section 6, PR 34). Numerics are allclose, not
  bitwise, vs the XLA path (online softmax reassociates the reduction):
  on the chip the engine is held by the benchmark's check against the
  float32 reference and by ``chip_smoke.py``'s kernel-against-XLA phase.

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
``_gather_attend`` for the XLA spelling; the Pallas kernel widens the
int8 pages in VMEM (exactly) and multiplies the block's scores by the
K pages' scales and its weights by the V pages' (the same product, with
one rounding fewer). The f32 path is
bit-for-bit untouched (no cast, no extra ops) — the engine's bitwise
parity contract only ever applied to unquantized pools, and still
does.

Latent pools (ISSUE 37; the second half of this file): a latent-attention
model caches one row a token a layer, ``[L, P, W, ps]``, and its reads
(``index_scores``, ``select_threshold``, ``selected_latent_attention``,
``window_latent_attention``) walk a row's own pages as the ragged kernel
does. ``selected_latent_attention`` has the same two spellings behind one
entry point as ``ragged_paged_attention`` (ISSUE 39), picked where the
program is traced, by platform and shapes (``latent_attention_path``):
the XLA walk, whose float32 score blocks ``[256, 128, 2048]`` go through
HBM five times a block (on a v5e 6.8 ms for one layer's chunk of 256
queries with 8,960 positions behind it, 2.6 ms for twelve decode rows of
which six are live at 13-21 k: every row walks as far as the longest), and
the Pallas kernel ``selected_latent_attn``, whose scores stay in VMEM
(4.2 ms and 0.44 ms; 16.4 -> 8.6 ms of the dots3 cell's tick; PERF.md
section 6, PR 39). ``latent_attention`` (ISSUE 40) is the same pair without
a selection: every visible position of the row, DeepSeek-V2's dense MLA.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_paged_attention", "paged_kv_scatter", "latent_scatter",
           "index_scores", "select_topk", "select_threshold",
           "selection_mask", "selected_latent_attention",
           "latent_attention", "window_latent_attention"]

_NEG_INF = -1e9     # same masking constant as gpt_cached_apply


def _interpret() -> bool:
    from ..core.place import target_platform

    return target_platform() == "cpu"


def resolve_impl(impl=None) -> str:
    """``"pallas"`` or ``"xla"``: ``impl`` itself when given, else the
    platform's, where the program is being traced (the kernel where it
    is compiled for a TPU, the XLA spelling anywhere else)."""
    if impl is not None:
        return impl
    from ..core.place import target_platform

    return "pallas" if target_platform() == "tpu" else "xla"


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
                           impl=None, k_scale=None,
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
    impl        None           the platform's (``resolve_impl``), or
                               ``"xla"`` / ``"pallas"``; counted, while
                               the program is traced, in
                               ``serving/attn_calls{path=}``

    Query ``i`` of row ``r`` attends cache positions
    ``<= pos0[r] + i``. Rows are fixed-shape: queries at
    ``i >= true_len[r]`` are computed anyway and produce garbage the
    caller must ignore (on the Pallas path their trailing page blocks
    are additionally skipped, so the garbage differs between impls —
    never compare pad queries). Returns [R, T, NH, D].
    """
    from ..profiler import metrics

    impl = resolve_impl(impl)
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown paged attention impl {impl!r}")
    metrics.registry().counter(
        "serving/attn_calls{path=%s}" % impl).add(1)
    if impl == "xla":
        t = q.shape[1]
        qpos = pos0[:, None] + jnp.arange(t, dtype=pos0.dtype)[None, :]
        return _gather_attend(q, k_pool, v_pool, page_table, qpos,
                              k_scale=k_scale, v_scale=v_scale,
                              layer=layer)
    return _ragged_attention_pallas(q, k_pool, v_pool, page_table, pos0,
                                    true_len, k_scale=k_scale,
                                    v_scale=v_scale, layer=layer)


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

#: tokens of one KV block: what one trip of the kernel's loop fetches
#: and multiplies (several pages; a v5e moves it in about 2.5 us)
_BLOCK_TOKENS = 256


def kv_block_pages(page_size: int, pages_per_slot: int) -> int:
    """Pages in one KV block of the kernel, from the static shapes."""
    return max(1, min(pages_per_slot, _BLOCK_TOKENS // page_size))


def live_block_share(pos0, true_len, page_size: int,
                     pages_per_slot: int) -> float:
    """Blocks the kernel's loops visit for rows ``(pos0, true_len)``
    (host arrays) over the blocks of the same rows at capacity."""
    bt = kv_block_pages(page_size, pages_per_slot) * page_size
    cap = page_size * pages_per_slot
    pos0, true_len = np.asarray(pos0), np.asarray(true_len)
    live = np.where(true_len > 0, np.minimum(pos0 + true_len, cap), 0)
    return float((-(-live // bt)).sum()) / (len(live) * -(-cap // bt))


def _rows_per_word(dtype) -> int:
    return 4 // jnp.dtype(dtype).itemsize


def _heads(ref, nh: int):
    """Every head of ``ref`` ``[..., NH, D]`` (a VMEM ref whose heads fill
    whole tiles), each as ``[rows, D]``: head ``h`` is row ``t * NH + h``
    of the flat view for every ``t``, one strided load. 16-bit rows come
    two to a 32-bit word, so a load brings two heads, taken apart with a
    shift and a mask."""
    hd = ref.shape[-1]
    rows = math.prod(ref.shape[:-1]) // nh
    flat = ref.reshape(rows * nh, hd)
    w = _rows_per_word(ref.dtype)
    if w == 1:
        return [flat[pl.ds(h, rows, stride=nh), :] for h in range(nh)]
    words = flat.bitcast(jnp.uint32)
    out = []
    for h in range(nh // w):
        pair = words[pl.ds(h, rows, stride=nh // w), :]
        out += [pltpu.bitcast(x, jnp.float32).astype(ref.dtype)
                for x in (pair << 16, pair & jnp.uint32(0xFFFF0000))]
    return out


def _dot(a, b, dims):
    """A product of the kernel, float32 accumulated. 16-bit operands go to
    the MXU as they are whatever ``jax_default_matmul_precision`` says
    (Mosaic refuses a bf16 product at ``highest``)."""
    precision = jax.lax.Precision.DEFAULT if a.dtype.itemsize == 2 else None
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _ragged_kernel(pt_ref, pos0_ref, tl_ref, layer_ref, q_ref, k_hbm, v_hbm,
                   *rest, t: int, quant: bool, split: bool):
    """Grid (r,): row ``r``. The pools stay in HBM; the KV axis is a
    loop inside the kernel over blocks of ``bp`` pages whose trip count
    is the row's own ``ceil(kv_len / block)``, so a page past the row's
    last attendable position is never visited, and a row of length 0
    costs the grid step alone. Each block's live pages are fetched by
    page id with the kernel's own asynchronous copies into one of two
    buffers; the next block, or the next row's first, is in flight
    while this one is multiplied (the buffer in turn is carried from
    row to row in SMEM, so the grid axis is sequential).

    ``split`` (the heads fill whole tiles) reads every head of q, K and
    V as its own ``[rows, D]`` by a strided load and runs two plain
    products a head on the MXU in the pools' type, float32 accumulated;
    otherwise (small head counts) the block is one batched product in
    float32. The online softmax is float32 either way. Pages narrower
    than the products' type are widened in VMEM (exactly); int8 pages'
    scale rows multiply the block's scores and weights:
    ``ks_ref``/``vs_ref`` hold the row's scale at every position."""
    rest = list(rest)
    ks_ref, vs_ref = (rest.pop(0), rest.pop(0)) if quant else (None, None)
    o_ref, kbuf, vbuf = rest[:3]
    kwide, vwide = rest[3:-5] or (None, None)
    sem, slot_ref, m_ref, l_ref, acc_ref = rest[-5:]
    streams = ((k_hbm, kbuf), (v_hbm, vbuf))
    _, bp, ps, nh, hd = kbuf.shape
    tp = q_ref.shape[1]
    nps = pt_ref.shape[1]
    bt = bp * ps
    r = pl.program_id(0)
    last_row = r + 1 == pl.num_programs(0)
    layer = layer_ref[0]

    def kv_len(row):
        n = jnp.minimum(pos0_ref[row] + tl_ref[row], nps * ps)
        return jnp.where(tl_ref[row] > 0, n, 0)

    def copies(row, blk, slot, act):
        """``act`` (start or wait) on the copy of every live page of
        block ``blk`` of ``row`` into buffer ``slot``."""
        first = blk * bp
        count = jnp.minimum(pl.cdiv(kv_len(row), ps) - first, bp)

        def one(i, carry):
            page = pt_ref[row, first + i]
            for hbm, buf in streams:
                act(pltpu.make_async_copy(hbm.at[layer, page],
                                          buf.at[slot, i], sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, count, one, 0)

    start = lambda c: c.start()
    wait = lambda c: c.wait()
    n_live = kv_len(r)
    nblk = pl.cdiv(n_live, bt)

    @pl.when(r == 0)
    def _first():
        slot_ref[0] = 0
        copies(r, 0, 0, start)

    slot0 = slot_ref[0]
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def block(b, carry):
        slot = (slot0 + b) % 2

        @pl.when(b + 1 < nblk)
        def _next_block():
            copies(r, b + 1, 1 - slot, start)

        @pl.when(jnp.logical_and(b + 1 == nblk, jnp.logical_not(last_row)))
        def _next_row():
            copies(r + 1, 0, 1 - slot, start)

        copies(r, b, slot, wait)
        ksrc, vsrc = kbuf.at[slot], vbuf.at[slot]
        if kwide is not None:
            # pages narrower than the products' type are widened here,
            # exactly (int8's scales go onto the scores and the weights)
            kwide[...] = ksrc[...].astype(jnp.float32).astype(kwide.dtype)
            vwide[...] = vsrc[...].astype(jnp.float32).astype(vwide.dtype)
            ksrc, vsrc = kwide, vwide
        if quant:       # the row's scales at this block, [NH, 1, bt]
            at = (0, slice(None), slice(None),
                  pl.ds(pl.multiple_of(b * bt, bt), bt))
        left = n_live - b * bt              # live positions of this block
        if split:
            s = jnp.stack([
                _dot(qh, kh, (((1,), (1,)), ((), ())))
                for qh, kh in zip(_heads(q_ref.at[0], nh),
                                  _heads(ksrc, nh))])
        else:
            q = q_ref[0].astype(jnp.float32)            # [Tp, NH, D]
            k = ksrc[...].astype(jnp.float32).reshape(bt, nh, hd)
            s = _dot(q, k, (((2,), (2,)), ((1,), (1,))))  # [NH, Tp, bt]
        s = s / math.sqrt(hd)
        if quant:
            s = s * ks_ref[at]
        # query i attends positions <= pos0 + i, and none past the row's
        # last live one (a pad query would read what no copy fetched)
        kpos = b * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        qpos = pos0_ref[r] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = kpos <= jnp.minimum(qpos, n_live - 1)
        s = jnp.where(keep, s, _NEG_INF)
        m_prev = m_ref[:]                               # [NH, Tp, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)                          # [NH, Tp, bt]
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = corr * l_ref[:] + jnp.sum(p, axis=2, keepdims=True)
        m_ref[:] = m_new
        if quant:
            p = jnp.where(keep, p * vs_ref[at], 0.0)
        # rows past the live ones hold what an earlier block left there,
        # or nothing at all: 0 x NaN must not reach the accumulator
        if split:
            dead = jax.lax.broadcasted_iota(jnp.int32, (bt, hd), 0) >= left
            pv = jnp.stack([
                _dot(p[h].astype(vh.dtype),
                     jnp.where(dead, jnp.zeros_like(vh), vh),
                     (((1,), (0,)), ((), ())))
                for h, vh in enumerate(_heads(vsrc, nh))])
        else:
            v = vsrc[...].astype(jnp.float32).reshape(bt, nh, hd)
            dead = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) >= left
            pv = _dot(p, jnp.where(dead, 0.0, v),
                      (((2,), (0,)), ((0,), (1,))))     # [NH, Tp, D]
        acc_ref[:] = corr * acc_ref[:] + pv
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)

    @pl.when(jnp.logical_and(nblk == 0, jnp.logical_not(last_row)))
    def _next_row_of_an_empty_one():
        copies(r + 1, 0, slot0, start)

    slot_ref[0] = (slot0 + nblk) % 2
    # a row of length 0 gets zeros, not 0/0: it is never read, but NaN
    # would trip debug_nans and pollute allclose diagnostics
    l = l_ref[:]
    out = acc_ref[:] / jnp.where(l == 0.0, 1.0, l)      # [NH, Tp, D]
    if split:
        for h in range(nh):     # o_ref [1, T * NH, D]: row t * NH + h
            o_ref[0, pl.ds(h, t, stride=nh), :] = out[h, :t]
    else:
        o_ref[0] = jnp.transpose(out, (1, 0, 2))[:t].astype(o_ref.dtype)


def _ragged_attention_pallas(q, k_pool, v_pool, page_table, pos0,
                             true_len, k_scale=None, v_scale=None,
                             layer=None):
    r, t, nh, hd = q.shape
    ps = k_pool.shape[-3]
    nps = page_table.shape[1]
    bp = kv_block_pages(ps, nps)
    quant = k_scale is not None
    if layer is None:
        # one layer's pools are a stack of one (a reshape): the kernel
        # has one spelling, the stacked one
        layer = 0
        k_pool, v_pool = k_pool[None], v_pool[None]
        if quant:
            k_scale, v_scale = k_scale[None], v_scale[None]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    # what the products run in: the pools' type, int8 as the queries'
    # (mixed float types at the wider of the two, as the XLA spelling)
    kv_dtype = q.dtype if quant else \
        jnp.promote_types(k_pool.dtype, q.dtype)
    rows = 8 * _rows_per_word(kv_dtype)     # of one tile of that type
    split = nh % rows == 0
    # the queries ride at a whole tile's rows: a one-row product is not
    # the MXU's, and the pad rows cost it nothing
    tp = -(-t // rows) * rows if split else t
    qk = q.astype(kv_dtype)
    if tp != t:
        qk = jnp.pad(qk, ((0, 0), (0, tp - t), (0, 0), (0, 0)))

    # the split kernel writes head h of query i at row i * NH + h
    o_shape = (t * nh, hd) if split else (t, nh, hd)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, tp, nh, hd),
                             lambda i, pt, p0, tl, ly: (i, 0, 0, 0)),
                hbm, hbm]
    args = (page_table, pos0, true_len, layer, qk, k_pool, v_pool)
    scratch = [pltpu.VMEM((2, bp, ps, nh, hd), k_pool.dtype),
               pltpu.VMEM((2, bp, ps, nh, hd), v_pool.dtype)]
    if quant:
        # the scale of every position of every row, [R, NH, 1, blocks *
        # bt] (a gather of R * NPs rows of NH numbers, not of pages): a
        # row's block of them multiplies its scores and its weights
        pt = jnp.pad(page_table, ((0, 0), (0, -nps % bp)))
        in_specs += [pl.BlockSpec(
            (1, nh, 1, pt.shape[1] * ps),
            lambda i, pt, p0, tl, ly: (i, 0, 0, 0))] * 2
        args += tuple(
            jnp.repeat(jnp.swapaxes(sc[layer[0], pt], 1, 2), ps,
                       axis=2)[:, :, None] for sc in (k_scale, v_scale))
    if k_pool.dtype != kv_dtype:
        scratch += [pltpu.VMEM((bp, ps, nh, hd), kv_dtype)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((nh, tp, 1), jnp.float32),
                pltpu.VMEM((nh, tp, 1), jnp.float32),
                pltpu.VMEM((nh, tp, hd), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1,) + o_shape,
            lambda i, pt, p0, tl, ly: (i,) + (0,) * len(o_shape)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, t=t, quant=quant, split=split),
        name="ragged_paged_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r,) + o_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(*args)
    return out.reshape(q.shape).astype(q.dtype)


# --------------------------------------------------------------------------
# latent pools (ISSUE 37): one vector a token a layer in place of K and V
# --------------------------------------------------------------------------
# A latent-attention model (MLA, arXiv:2405.04434) caches ``(c_kv,
# k_rope)``, one row of ``C + R`` numbers a token a layer that every head
# shares; attention runs in the absorbed form (the queries carried into
# the latent space, the values carried out of it afterwards), so a pool has
# no head axis. It is ``[L, P, W, ps]``, a page's tokens along the *last*
# axis, which is how the reads below want it (keys on the lanes of their
# products). A write of one token's row is then a column of its page, and
# XLA:TPU re-lays the whole pool for a scatter of columns and back (three
# copies of the 0.93 GB latent pool a tick, 9 ms of 44 on a v5e, with either
# order of the two axes; PERF.md section 6, PR 37). So the writes go a page
# at a time (``latent_scatter``): the pages a tick touches are read, given
# their new columns and written back whole, which is the pool's own layout.
# The functions below are the read and write sides of such pools: the same
# walk over a row's own pages as the ragged kernel's, other contents. Every
# shape is fixed, every trip count is the rows' own. All are ``jax.numpy``
# but the full layers' attention, ``selected_latent_attention``, which on
# the chip is the Pallas kernel at the end of this file (ISSUE 39): a page
# ``[W, ps]`` is what ``q @ page`` wants as its right-hand side, so the
# kernel fetches pages by id as they lie and nothing is re-laid.

#: pages of one block of ``index_scores``' walk over a row's indexer keys
_INDEX_BLOCK_PAGES = 8


def latent_scatter(pool, page, off, vals, layer, touched=None):
    """Each token's row ``vals`` [NT, W] written at its ``(layer, page,
    off)`` of ``pool`` [L, P, W, ps] (null page 0 for rows that write
    nothing). ``touched`` [n] names every page a token writes to, in any
    order, as often as it likes and padded with the null page (a tick knows
    them: a decode row's page and the few a chunk spans; left out: every
    token's own). Those pages are read, each takes the columns of *all* the
    tokens that write to it (so a page named twice is written twice with the
    same contents) and goes back whole. The stack is written in place and
    returned."""
    ps = pool.shape[-1]
    vals = vals if vals.dtype == pool.dtype else vals.astype(pool.dtype)
    touched = page if touched is None else touched
    hit = (page[None, None, :] == touched[:, None, None]) \
        & (off[None, None, :] == jnp.arange(ps, dtype=off.dtype)[None, :, None])
    # one token at most writes a column: a sum over one term, exact
    new = _einsum_f32("tw,qot->qwo", vals, hit.astype(vals.dtype))
    old = pool[layer, touched]                              # [n, W, ps]
    return pool.at[layer, touched].set(
        jnp.where(jnp.any(hit, axis=-1)[:, None, :], new.astype(pool.dtype),
                  old))


def _einsum_f32(spec: str, a, b):
    """``einsum`` accumulated and returned in float32. The CPU's dot has
    no 16-bit x 16-bit -> float32 form for every contraction, so there the
    operands are widened first (the same numbers: a product of two bf16
    values is exact in float32)."""
    if a.dtype.itemsize == 2 and _interpret():
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _block_of_pages(pool, layer, pages):
    """Pages ``pages`` [R, n] of ``pool`` as ``[R, W, n * ps]``: the rows'
    tokens side by side along the last axis."""
    got = pool[layer, pages]                            # [R, n, W, ps]
    r, n, width, ps = got.shape
    return jnp.swapaxes(got, 1, 2).reshape(r, width, n * ps)


def index_scores(q_i, w_i, k_pool, layer, page_table, pos0, true_len):
    """The sparse indexer's scores of every query against its row's live
    keys: ``I(t, s) = sum_j w_j(t) relu(q_j(t) . k(s))`` (DeepSeek-V3.2's
    lightning indexer).

    q_i         [R, T, J, D]   index queries (T static, J index heads)
    w_i         [R, T, J]      each query's head weights
    k_pool      [L, P, D, ps]  the indexer keys' page pool
    page_table  [R, NPs]       page ids per row
    pos0, true_len [R]         as ``ragged_paged_attention``

    Returns float32 ``[R, T, NPs * ps]``: ``-inf`` at every position query
    ``i`` may not see (past ``pos0 + i``, or past the row's last live
    one). The keys are walked in blocks of ``_INDEX_BLOCK_PAGES`` pages
    and only as far as the longest row's live positions reach: a page
    past them is never read."""
    r, t = q_i.shape[:2]
    ps = k_pool.shape[-1]
    nps = page_table.shape[1]
    bp = min(_INDEX_BLOCK_PAGES, nps)
    blocks = -(-nps // bp)
    bt = bp * ps
    table = jnp.pad(page_table, ((0, 0), (0, blocks * bp - nps)))
    live = jnp.where(true_len > 0, jnp.minimum(pos0 + true_len, nps * ps), 0)
    qpos = pos0[:, None] + jnp.arange(t, dtype=pos0.dtype)[None, :]
    last = jnp.minimum(qpos, live[:, None] - 1)             # [R, T]
    w_f = w_i.astype(jnp.float32)

    def block(b, out):
        pages = jax.lax.dynamic_slice(table, (0, b * bp), (r, bp))
        kpos = b * bt + jnp.arange(bt, dtype=pos0.dtype)
        k = jnp.where((kpos[None, :] < live[:, None])[:, None, :],
                      _block_of_pages(k_pool, layer, pages), 0)
        s = _einsum_f32("rtjd,rds->rtjs", q_i, k.astype(q_i.dtype))
        s = jnp.sum(jax.nn.relu(s) * w_f[..., None], axis=2)  # [R, T, bt]
        s = jnp.where(kpos[None, None, :] <= last[:, :, None], s, -jnp.inf)
        return jax.lax.dynamic_update_slice(out, s, (0, 0, b * bt))

    out = jnp.full((r, t, blocks * bt), -jnp.inf, jnp.float32)
    n_live = jnp.minimum(-(-jnp.max(live) // bt), blocks)
    out = jax.lax.fori_loop(0, n_live, block, out)
    return out[:, :, :nps * ps]


def select_topk(scores, k: int):
    """The ``k`` largest of each row of ``scores`` [N, S] (``-inf``: not
    visible): ``(idx [N, k] int32, valid [N, k])``, fixed-shape, ``valid``
    false where fewer than ``k`` are visible. Exact (``lax.top_k``, which
    the TPU compiles to a sort of the whole row: 9.8 ms for ``[268,
    33792]`` on a v5e, PERF.md section 6, PR 37), so no tick calls it:
    every row's selection is ``select_threshold``'s, and this is what the
    tests and ``chip_smoke.py`` hold that to."""
    val, idx = jax.lax.top_k(scores, min(k, scores.shape[-1]))
    return idx.astype(jnp.int32), val > -jnp.inf


def _ordered_bits(scores):
    """float32 ``scores`` as uint32 that compare as the floats do
    (``-inf`` lowest)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(key, jnp.uint32) \
        ^ jnp.uint32(0x80000000)


def select_threshold(scores, k: int):
    """Each row's selection as a threshold: ``(keys, thr, ties)`` with
    ``keys`` uint32 ``[N, S]`` (the scores, order kept), ``thr`` uint32
    ``[N]`` the row's ``k``-th largest (0 where fewer than ``k`` are
    visible) and ``ties`` int32 ``[N]``: the row's selection is every
    position with ``keys > thr`` and the first ``ties`` positions with
    ``keys == thr``, which is ``lax.top_k``'s set, its ties broken towards
    the lower position too. No sort and no gather: the ``k``-th largest is
    found a bit at a time, 32 passes of a comparison and a count over the
    scores."""
    keys = _ordered_bits(scores)

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        enough = jnp.sum(keys >= cand[:, None], axis=1) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros((scores.shape[0],), jnp.uint32))
    above = jnp.sum(keys > thr[:, None], axis=1).astype(jnp.int32)
    return keys, thr, k - above


def selection_mask(keys, thr, ties):
    """``select_threshold``'s selection as a mask ``[N, S]`` (over whole
    rows: the walk of ``selected_latent_attention`` does the same a block
    at a time)."""
    tie = keys == thr[:, None]
    first = jnp.cumsum(tie, axis=1) <= ties[:, None]
    return (keys > thr[:, None]) | (tie & first)


#: pages of one block of the XLA spelling's walk
_ATTN_BLOCK_PAGES = 16


def latent_attention_path(q, pool, c_width: int, impl=None) -> str:
    """``"pallas"`` or ``"xla"`` for ``selected_latent_attention``:
    ``impl`` itself when given; else the kernel where the program is traced
    for a TPU (``resolve_impl``) *and* the shapes are ones Mosaic tiles
    (pages of whole lanes, heads and widths of whole sublane tiles, a tile
    of queries that fits), the XLA spelling anywhere else. Nothing else
    selects the path."""
    if impl is not None:
        return impl
    if resolve_impl(None) == "xla":
        return "xla"
    t, nh, width = q.shape[1:]
    rows = 8 * _rows_per_word(pool.dtype)
    tiles = (q.dtype == pool.dtype and pool.shape[-1] % 128 == 0
             and c_width % 128 == 0 and nh % rows == 0 and width % rows == 0
             and _latent_tile_queries(t, nh) * nh <= 2 * _LATENT_TILE_ROWS)
    return "pallas" if tiles else "xla"


def selected_latent_attention(q, pool, layer, page_table, pos0, true_len,
                              keys, thr, ties, c_width: int, scale: float,
                              impl=None):
    """Absorbed (multi-query) attention of ragged rows over a latent pool,
    each query over its own *selection* of its row's live positions.

    q           [R, T, NH, W]  queries in the latent space: ``q_nope
                               W_kvb^K`` (``c_width`` wide) beside the
                               rotated ``q_rope``
    pool        [L, P, W, ps]  latents ``(c_kv, k_rope)``
    page_table  [R, NPs]       page ids per row
    keys        [R, T, S]      ``select_threshold``'s, with ``thr`` and
    thr, ties   [R, T]         ``ties``: which positions query ``i`` of row
                               ``r`` selected (of those ``<= pos0[r] + i``
                               within the row's live positions)
    impl        None           the path ``latent_attention_path`` observes,
                               or ``"xla"`` / ``"pallas"``; counted, while
                               the program is traced, in
                               ``serving/latent_attn_calls{path=}``

    The selected latents are never gathered a query at a time (549 k rows
    of 1,152 B took 9.8 ms a layer on a v5e, and their page ids 5.6 more;
    PERF.md section 6, PR 37): every head of a tile of queries scores whole
    pages under the selection's mask and a float32 online softmax, at the
    price of scoring what is not selected. Two spellings of that walk:

    - ``"xla"`` (the reference, and what anything but a TPU runs):
      ``_selected_latent_xla``, a ``fori_loop`` over blocks of
      ``_ATTN_BLOCK_PAGES`` pages as far as the *longest* row's live
      positions, every block's float32 scores ``[R, T, NH, 2048]`` written
      to HBM and read back for the mask, the maximum, the exponentials and
      the second product, and the decode rows' page blocks re-laid by
      ``_block_of_pages``. On a v5e, one layer: 1.8 / 6.8 / 11.8 ms for a
      chunk of 256 with 0 / 8,960 / 16,384 positions behind it, 2.6 ms for
      twelve decode rows, six of them live at 13-21 k (PERF.md section 6,
      PR 39).
    - ``"pallas"`` (the chip's, ISSUE 39): the kernel
      ``selected_latent_attn``. Grid (row, tile of queries); the pool stays
      in HBM and a block's pages come by page id into one of two VMEM
      buffers; scores, mask and softmax never leave VMEM; a tile walks only
      as far as its own last query sees. The same calls: 0.59 / 4.2 /
      7.3 ms and 0.44 ms, the products at about 160 TFLOP/s of the chip's
      197 behind a long context; ``mla.attn_ms_per_tick`` of
      ``serve-dots3-longdoc-backlog`` 16.4 -> 8.6 ms (two layers).
      Allclose, not bitwise, to the spelling (the blocks differ, so the
      online softmax reassociates); both read 3-4e-3 of the largest value
      off a float32 softmax at the cell's shapes.

    Returns ``[R, T, NH, c_width]`` (the values are carried out of the
    latent space by the caller); a query with nothing to attend gets zeros.
    Queries at ``i >= true_len[r]`` are computed anyway and hold garbage
    that differs between the spellings: never compare pad queries."""
    from ..profiler import metrics

    impl = latent_attention_path(q, pool, c_width, impl)
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown latent attention impl {impl!r}")
    metrics.registry().counter(
        "serving/latent_attn_calls{path=%s}" % impl).add(1)
    spelling = _selected_latent_xla if impl == "xla" \
        else _selected_latent_pallas
    return spelling(q, pool, layer, page_table, pos0, true_len, keys, thr,
                    ties, c_width, scale)


def latent_attention(q, pool, layer, page_table, pos0, true_len,
                     c_width: int, scale: float, impl=None):
    """Absorbed (multi-query) attention of ragged rows over a latent pool,
    **dense**: query ``i`` of row ``r`` attends every live position ``s <=
    pos0[r] + i`` of its row (DeepSeek-V2's MLA, arXiv:2405.04434 section
    2.1: no indexer, no selection), so its cost grows with the context
    where ``selected_latent_attention``'s is capped.

    The arguments, the result and the two spellings are
    ``selected_latent_attention``'s without ``keys``, ``thr`` and ``ties``:
    the same XLA walk (the reference) and the same Pallas kernel scheme
    (``latent_attn``: the row's own live pages by page id, two buffers, an
    online softmax in VMEM, nothing of extent heads x keys in HBM) with the
    causal mask alone, no selection operand and no tie pass. Chunk rows run
    absorbed as decode rows do: one kernel for both, at ``2 NH (W + C)``
    operations a visible pair where expanding ``k_nope`` and ``v`` from the
    latents would take ``2 NH (192 + 128)`` a pair and ``2 C NH 256`` a
    visible key a call, 0.56 against 0.73 TFLOP for a chunk of 256 behind
    10 k: too little to pay for a second kernel and the expanded keys'
    round trip through HBM (PERF.md section 6, PR 40). The path is
    ``latent_attention_path``'s and counted in
    ``serving/latent_attn_calls{path=,kind=dense}``."""
    from ..profiler import metrics

    impl = latent_attention_path(q, pool, c_width, impl)
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown latent attention impl {impl!r}")
    metrics.registry().counter(
        "serving/latent_attn_calls{path=%s,kind=dense}" % impl).add(1)
    spelling = _selected_latent_xla if impl == "xla" \
        else _selected_latent_pallas
    return spelling(q, pool, layer, page_table, pos0, true_len, None, None,
                    None, c_width, scale)


def _selected_latent_xla(q, pool, layer, page_table, pos0, true_len,
                         keys, thr, ties, c_width: int, scale: float):
    """``selected_latent_attention`` in ``jax.numpy``: the row's live pages
    walked once, in blocks of ``_ATTN_BLOCK_PAGES`` pages, every head of
    every query of the row scoring a block's latents in one product.
    ``keys`` None: no selection, every visible position
    (``latent_attention``)."""
    dense = keys is None
    r, t, nh = q.shape[:3]
    ps = pool.shape[-1]
    nps = page_table.shape[1]
    bp = min(_ATTN_BLOCK_PAGES, nps)
    blocks = -(-nps // bp)
    bt = bp * ps
    table = jnp.pad(page_table, ((0, 0), (0, blocks * bp - nps)))
    if not dense:
        keys = jnp.pad(keys,
                       ((0, 0), (0, 0), (0, blocks * bt - keys.shape[2])))
    live = jnp.where(true_len > 0, jnp.minimum(pos0 + true_len, nps * ps), 0)
    qpos = pos0[:, None] + jnp.arange(t, dtype=pos0.dtype)[None, :]
    last = jnp.minimum(qpos, live[:, None] - 1)             # [R, T]

    def block(b, carry):
        m, l, acc, left = carry
        pages = jax.lax.dynamic_slice(table, (0, b * bp), (r, bp))
        kpos = b * bt + jnp.arange(bt, dtype=pos0.dtype)
        # what lies past the row's live positions is whatever was there:
        # zeros, so that a weight of 0 cannot meet a NaN
        lat = jnp.where((kpos[None, :] < live[:, None])[:, None, :],
                        _block_of_pages(pool, layer, pages), 0)
        lat = lat if lat.dtype == q.dtype else lat.astype(q.dtype)
        s = _einsum_f32("rtnc,rcs->rtns", q, lat) * scale
        if not dense:
            mine = jax.lax.dynamic_slice(keys, (0, 0, b * bt), (r, t, bt))
        seen = kpos[None, None, :] <= last[:, :, None]
        if dense:
            keep = seen[:, :, None, :]
        else:
            tie = seen & (mine == thr[:, :, None])
            taken = tie & (jnp.cumsum(tie, axis=-1) <= left[:, :, None])
            keep = ((seen & (mine > thr[:, :, None]))
                    | taken)[:, :, None, :]
        s = jnp.where(keep, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(keep, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = corr * l + jnp.sum(p, axis=-1)
        acc = corr[..., None] * acc + _einsum_f32(
            "rtns,rcs->rtnc", p.astype(q.dtype), lat[:, :c_width])
        return m_new, l, acc, left if dense else \
            left - jnp.sum(tie, axis=-1).astype(left.dtype)

    n_live = jnp.minimum(-(-jnp.max(live) // bt), blocks)
    _, l, acc, _ = jax.lax.fori_loop(0, n_live, block, (
        jnp.full((r, t, nh), _NEG_INF, jnp.float32),
        jnp.zeros((r, t, nh), jnp.float32),
        jnp.zeros((r, t, nh, c_width), jnp.float32),
        jnp.zeros((), jnp.int32) if dense else ties.astype(jnp.int32)))
    return (acc / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(q.dtype)


def window_latent_attention(q, pool, layer, page_table, pos0, true_len,
                            window: int, c_width: int, scale: float):
    """Absorbed attention of ragged rows over the last ``window`` positions
    of a latent pool: query ``i`` of row ``r`` sees ``pos0[r] + i - window
    < s <= pos0[r] + i``.

    q           [R, T, NH, W]  per-row query blocks (T static)
    pool        [L, P, W, ps]  the windowed layers' latents
    page_table  [R, NPs]       page ids per row; entries behind the window
                               may be null (their pages were given back)

    Only the pages that can hold a visible position are fetched: ``ceil((
    window - 1 + T) / ps) + 1`` from the page of the first query's oldest
    visible position on. Returns ``([R, T, NH, c_width], lse [R, T])``:
    ``lse`` float32, the log of the sum of a query's exponentiated scores,
    mean over its heads (it grows with the log of the keys a query sees,
    which tells a window from a longer one)."""
    r, t = q.shape[:2]
    ps = pool.shape[-1]
    nps = page_table.shape[1]
    wp = min(nps, -(-(window - 1 + t) // ps) + 1)
    first = jnp.maximum(pos0 - (window - 1), 0) // ps       # [R]
    cols = first[:, None] + jnp.arange(wp, dtype=pos0.dtype)[None, :]
    pages = jnp.where(
        cols < nps,
        jnp.take_along_axis(page_table, jnp.minimum(cols, nps - 1), axis=1),
        0)
    kpos = first[:, None] * ps + jnp.arange(wp * ps, dtype=pos0.dtype)
    qpos = pos0[:, None] + jnp.arange(t, dtype=pos0.dtype)[None, :]
    live = jnp.where(true_len > 0, pos0 + true_len, 0)
    # pages behind the window may be gone, positions past the live ones
    # hold whatever was there: zeros, so that a weight of 0 meets no NaN
    held = (kpos < live[:, None]) & (kpos > pos0[:, None] - window)
    lat = jnp.where(held[:, None, :], _block_of_pages(pool, layer, pages), 0)
    lat = lat if lat.dtype == q.dtype else lat.astype(q.dtype)
    k3, q3 = kpos[:, None, :], qpos[:, :, None]
    keep = (k3 <= q3) & (k3 > q3 - window) & (k3 < live[:, None, None])
    s = _einsum_f32("rtnc,rcs->rnts", q, lat) * scale
    s = jnp.where(keep[:, None], s, _NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)                      # [R, NH, T]
    p = jnp.exp(s - lse[..., None]).astype(q.dtype)
    return (jnp.einsum("rnts,rcs->rtnc", p, lat[:, :c_width]),
            jnp.mean(lse, axis=1))


# --------------------------------------------------------------------------
# Pallas kernel of the selected latent attention (ISSUE 39)
# --------------------------------------------------------------------------

#: positions of one block of the kernel's walk: what one trip fetches (four
#: pages of 128, 590 KB of latents) and scores
_LATENT_BLOCK_TOKENS = 512
#: rows of a tile's products, queries x heads (absorbed attention is
#: multi-query: every head of a query meets the same latents). On a v5e,
#: one layer's chunk of 256 behind 16,384 positions: 7.34 ms at 2,048 rows
#: x 512 positions, 7.62 at 1,024 x 512, 7.58 at 2,048 x 1,024, 8.62 at
#: 1,024 x 256 (PERF.md section 6, PR 39)
_LATENT_TILE_ROWS = 2048


def _latent_tile_queries(t: int, nh: int) -> int:
    """Queries of one tile of a row of ``t``: a divisor of ``t`` in whole
    sublane tiles whose ``tq * nh`` rows stay within ``_LATENT_TILE_ROWS``,
    or all of a short row."""
    want = max(1, _LATENT_TILE_ROWS // nh)
    fits = [d for d in range(8, min(t, want) + 1, 8) if t % d == 0]
    return t if t <= want or not fits else max(fits)


def _last_taken_tie(keys, thr, ties, last, group: int):
    """The position of the last tie each query takes: its selection is the
    visible positions with ``keys > thr`` and those with ``keys == thr`` up
    to that position (-1: no tie taken; ``S``: all of them), which is
    ``selection_mask``'s running count without a cumulative sum over ``S``:
    one pass counts the ties of every ``group`` positions, the group that
    holds the ``ties``-th is looked at alone.

    keys [R, T, S] uint32, thr uint32 / ties int32 / last int32 [R, T]
    (``last``: the last position a query sees), ``S`` a multiple of
    ``group``. Returns int32 [R, T]."""
    r, t, s = keys.shape
    n = s // group
    grouped = keys.reshape(r, t, n, group)
    at = jnp.arange(group, dtype=jnp.int32)
    kpos = (jnp.arange(n, dtype=jnp.int32) * group)[:, None] + at[None, :]
    tie = (grouped == thr[..., None, None]) \
        & (kpos <= last[..., None, None])
    per = jnp.sum(tie, axis=-1, dtype=jnp.int32)                # [R, T, n]
    cum = jnp.cumsum(per, axis=-1)
    grp = jnp.sum(cum < ties[..., None], axis=-1, dtype=jnp.int32)
    g = jnp.minimum(grp, n - 1)[..., None]
    need = ties - (jnp.take_along_axis(cum, g, -1)
                   - jnp.take_along_axis(per, g, -1))[..., 0]
    mine = jnp.take_along_axis(grouped, g[..., None], axis=2)[:, :, 0]
    pos = g * group + at                                        # [R, T, group]
    tie = (mine == thr[..., None]) & (pos <= last[..., None])
    taken = tie & (jnp.cumsum(tie, axis=-1) <= need[..., None])
    cut = jnp.max(jnp.where(taken, pos, -1), axis=-1)
    return jnp.where(ties <= 0, -1, jnp.where(grp >= n, s, cut))


def _latent_kernel(pt_ref, pos0_ref, tl_ref, layer_ref, q_ref, keys_ref,
                   thr_ref, cut_ref, pool_hbm, o_ref, buf, sem, slot_ref,
                   s_ref, p_ref, m_ref, l_ref, corr_ref, acc_ref, *,
                   scale: float, c_width: int, ps: int):
    """Grid (r, j): tile ``j`` of ``tq`` consecutive queries of row ``r``,
    every head of them the rows of its products (``tq * NH`` rows of ``W``).
    The pool stays in HBM; the key axis is a loop over blocks of ``bp`` of
    the row's own pages whose trip count is the tile's own: as far as its
    last real query sees, so a page past that is never read and a tile with
    no real query (a free slot's row, the pad tiles of a short chunk) costs
    the grid step alone. A block's pages are fetched by page id, side by
    side along the lanes of one of two buffers ``[W, bp * ps]``, the next
    block (or the next tile's first) in flight while this one is multiplied;
    the buffer in turn is carried from step to step in SMEM, so the grid is
    sequential. A block's scores ``[tq * NH, bp * ps]`` live in VMEM, in
    float32; the selection's mask is made once a query from its ``keys``,
    ``thr`` and last taken tie, and laid over its heads; running maximum,
    sum and accumulator are float32, the weights meet the latents again in
    the pool's type. ``keys_ref`` None (``_dense_latent_kernel``): no
    selection, the causal mask alone."""
    _, width, bt = buf.shape
    tq, nh = q_ref.shape[1:3]
    bp = bt // ps
    nps = pt_ref.shape[1]
    rows = tq * nh
    r, j = pl.program_id(0), pl.program_id(1)
    tiles = pl.num_programs(1)
    last_step = jnp.logical_and(r + 1 == pl.num_programs(0), j + 1 == tiles)
    next_r = jnp.where(j + 1 < tiles, r, r + 1)
    next_j = jnp.where(j + 1 < tiles, j + 1, 0)
    layer = layer_ref[0]

    def visible(row, tile):
        """Positions the real queries of ``tile`` of ``row`` see between
        them (0: the tile has no real query)."""
        n = jnp.minimum(pos0_ref[row] + jnp.minimum((tile + 1) * tq,
                                                    tl_ref[row]), nps * ps)
        return jnp.where(tile * tq < tl_ref[row], n, 0)

    def copies(row, tile, blk, slot, act):
        """``act`` (start or wait) on the copy of every page block ``blk``
        of a tile needs into buffer ``slot``."""
        first = blk * bp
        count = jnp.minimum(pl.cdiv(visible(row, tile), ps) - first, bp)

        def one(i, carry):
            page = pt_ref[row, first + i]
            act(pltpu.make_async_copy(
                pool_hbm.at[layer, page],
                buf.at[slot, :, pl.ds(pl.multiple_of(i * ps, ps), ps)],
                sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, count, one, 0)

    start = lambda c: c.start()
    wait = lambda c: c.wait()
    n_vis = visible(r, j)
    nblk = pl.cdiv(n_vis, bt)

    @pl.when(jnp.logical_and(r == 0, j == 0))
    def _first():
        slot_ref[0] = 0
        copies(r, j, 0, 0, start)

    slot0 = slot_ref[0]
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0].reshape(rows, width)
    if keys_ref is not None:
        thr, cut = thr_ref[0], cut_ref[0]                       # [tq, 1]
    qpos = pos0_ref[r] + j * tq + jax.lax.broadcasted_iota(
        jnp.int32, (tq, bt), 0)
    last = jnp.minimum(qpos, n_vis - 1)

    def block(b, carry):
        slot = (slot0 + b) % 2

        @pl.when(b + 1 < nblk)
        def _next_block():
            copies(r, j, b + 1, 1 - slot, start)

        @pl.when(jnp.logical_and(b + 1 == nblk, jnp.logical_not(last_step)))
        def _next_tile():
            copies(next_r, next_j, 0, 1 - slot, start)

        copies(r, j, b, slot, wait)
        lat = buf.at[slot]
        left = n_vis - b * bt

        @pl.when(left < bt)
        def _dead():
            # what lies past the tile's last position is whatever the
            # buffer or the page held: zeros, so that a weight of 0 cannot
            # meet a NaN
            at = jax.lax.broadcasted_iota(jnp.int32, (width, bt), 1)
            lat[...] = jnp.where(at < left, lat[...], jnp.zeros_like(lat))

        s_ref[...] = _dot(q, lat[...].astype(q.dtype),
                          (((1,), (0,)), ((), ())))             # [rows, bt]
        kpos = b * bt + jax.lax.broadcasted_iota(jnp.int32, (tq, bt), 1)
        keep = kpos <= last
        if keys_ref is not None:
            mine = keys_ref[0, :, pl.ds(pl.multiple_of(b * bt, bt), bt)]
            keep = keep & ((mine > thr) | ((mine == thr) & (kpos <= cut)))
        bias = jnp.where(keep, 0.0, _NEG_INF)                   # [tq, bt]
        for i in range(tq):         # a query's heads share its mask
            at = slice(i * nh, (i + 1) * nh)
            s = s_ref[at, :] * scale + bias[i:i + 1, :]
            m_prev = m_ref[at, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # where nothing was kept yet m is the mask's constant and the
            # weights are 1: the first kept score's ``corr`` is exactly 0
            p = jnp.exp(s - m_new)
            corr_ref[at, :] = jnp.exp(m_prev - m_new)
            l_ref[at, :] = corr_ref[at, :] * l_ref[at, :] \
                + jnp.sum(p, axis=1, keepdims=True)
            m_ref[at, :] = m_new
            p_ref[at, :] = p.astype(p_ref.dtype)
        acc_ref[...] = corr_ref[...] * acc_ref[...] + _dot(
            p_ref[...], lat[:c_width, :].astype(q.dtype),
            (((1,), (1,)), ((), ())))                           # [rows, C]
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)

    @pl.when(jnp.logical_and(nblk == 0, jnp.logical_not(last_step)))
    def _next_tile_of_an_empty_one():
        copies(next_r, next_j, 0, slot0, start)

    slot_ref[0] = (slot0 + nblk) % 2
    # a query that kept nothing (its maximum is still the mask's constant)
    # gets zeros, as a tile that walked nothing does
    kept = m_ref[...] > _NEG_INF / 2
    out = acc_ref[...] / jnp.where(kept, l_ref[...], 1.0)
    o_ref[0] = jnp.where(kept, out, 0.0).reshape(
        tq, nh, c_width).astype(o_ref.dtype)


def _dense_latent_kernel(pt_ref, pos0_ref, tl_ref, layer_ref, q_ref,
                         pool_hbm, *rest, **sizes):
    """``_latent_kernel`` with no selection operand."""
    _latent_kernel(pt_ref, pos0_ref, tl_ref, layer_ref, q_ref, None, None,
                   None, pool_hbm, *rest, **sizes)


def _selected_latent_pallas(q, pool, layer, page_table, pos0, true_len,
                            keys, thr, ties, c_width: int, scale: float):
    """The kernel's call; ``keys`` None: ``latent_attention``'s, under the
    name ``latent_attn``, without the three selection operands."""
    dense = keys is None
    r, t, nh, width = q.shape
    ps = pool.shape[-1]
    nps = page_table.shape[1]
    bp = max(1, min(nps, _LATENT_BLOCK_TOKENS // ps))
    bt = bp * ps
    tq = _latent_tile_queries(t, nh)
    cap = nps * ps
    selection = ()
    if not dense:
        live = jnp.where(true_len > 0, jnp.minimum(pos0 + true_len, cap), 0)
        qpos = pos0[:, None] + jnp.arange(t, dtype=pos0.dtype)[None, :]
        cut = _last_taken_tie(keys, thr, ties.astype(jnp.int32),
                              jnp.minimum(qpos, live[:, None] - 1), ps)
        if cap % bt:        # a block of keys is sliced whole
            keys = jnp.pad(keys, ((0, 0), (0, 0), (0, -cap % bt)))
        selection = (keys, thr[..., None], cut[..., None])

    def tile(*block):
        return pl.BlockSpec((1, tq) + block, lambda i, j, *_: (i, j)
                            + (0,) * len(block))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r, t // tq),
        in_specs=[tile(nh, width)]
        + [tile(*x.shape[2:]) for x in selection]
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile(nh, c_width),
        scratch_shapes=[
            pltpu.VMEM((2, width, bt), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((tq * nh, bt), jnp.float32),     # scores
            pltpu.VMEM((tq * nh, bt), q.dtype),         # weights
            pltpu.VMEM((tq * nh, 1), jnp.float32),      # running maximum
            pltpu.VMEM((tq * nh, 1), jnp.float32),      # running sum
            pltpu.VMEM((tq * nh, 1), jnp.float32),      # a block's rescale
            pltpu.VMEM((tq * nh, c_width), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_dense_latent_kernel if dense else _latent_kernel,
                          scale=scale, c_width=c_width, ps=ps),
        name="latent_attn" if dense else "selected_latent_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, t, nh, c_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=_interpret(),
    )(page_table, pos0, true_len, jnp.asarray(layer, jnp.int32).reshape(1),
      q, *selection, pool)


# --------------------------------------------------------------------------
# grouped-query pages (ISSUE 54): fewer key/value heads than query heads
# --------------------------------------------------------------------------
# ``Pools`` keeps a page as ``[ps, NH, D]``, the heads on the sublanes, and
# the ragged kernel reads a head by a strided load when the heads fill whole
# tiles (16 rows of bf16). Four key/value heads fill no tile: rounded up to
# 16 a token's K and V would be four times their size. A grouped pool is
# ``[L, P, 2 KVH, ps, D]``: **a page's positions on the sublanes**, a head of
# a page of 16 one bf16 tile, K's heads and then V's in ONE array, so that a
# page is one contiguous 2 KVH x ps x D block that one copy fetches. Nothing
# is padded: Falcon-H1's 4 heads of 128 are 2,048 B a token a layer. A
# key/value head meets the ``G = NH / KVH`` query heads it serves in one
# product: their queries lie side by side on the rows of its left operand.
# Writes go a page at a time (``latent_scatter``'s way: a token's row is one
# sublane of a tile, and XLA:TPU re-lays a whole pool around a scatter of
# such rows): the pages a tick touches are read, given their new rows and
# written back whole. (This section stands at the file's end, its names
# appended to ``__all__`` here, so that no line above it moved: a Mosaic
# kernel's serialized body carries its operations' line numbers, and the
# accepted cells' programs are compared byte for byte,
# tools/lower_served_ticks.py. An edit inside this section moves the grouped
# kernel's own locations: ``--compare`` then reads the bodies without them.)

__all__ += ["grouped_paged_attention", "grouped_kv_scatter"]


def grouped_kv_scatter(pool, page, off, kk, vv, layer, touched=None):
    """Each token's keys and values ``kk``, ``vv`` [NT, KVH, D] written at
    its ``(layer, page, off)`` of ``pool`` [L, P, 2 KVH, ps, D] (null page 0
    for rows that write nothing). ``touched`` [n] names every page a token
    writes to, as ``latent_scatter`` takes it (left out: every token's own).
    The stack is written in place and returned."""
    ps = pool.shape[-2]
    vals = jnp.concatenate([kk, vv], axis=1)                # [NT, 2 KVH, D]
    vals = vals if vals.dtype == pool.dtype else vals.astype(pool.dtype)
    touched = page if touched is None else touched
    hit = (page[None, None, :] == touched[:, None, None]) \
        & (off[None, None, :] == jnp.arange(ps, dtype=off.dtype)[None, :, None])
    # one token at most writes a row: a sum over one term, exact
    new = _einsum_f32("thd,qot->qhod", vals, hit.astype(vals.dtype))
    old = pool[layer, touched]                              # [n, 2 KVH, ps, D]
    return pool.at[layer, touched].set(
        jnp.where(jnp.any(hit, axis=-1)[:, None, :, None],
                  new.astype(pool.dtype), old))


def _grouped_gather_attend(q, pool, page_table, qpos, layer):
    """``_gather_attend`` over a grouped pool: the rows' pages gathered into
    ``[R, KVH, S_cap, D]`` views of K and of V, every key/value head against
    its ``G`` query heads, the same mask constant and float32 softmax."""
    r, t, nh, hd = q.shape
    kvh, ps = pool.shape[-3] // 2, pool.shape[-2]
    g = nh // kvh
    got = pool[layer, page_table]                   # [R, NPs, 2 KVH, ps, D]
    if got.dtype != q.dtype:
        got = got.astype(jnp.promote_types(got.dtype, q.dtype))
    got = jnp.swapaxes(got, 1, 2).reshape(r, 2 * kvh, -1, hd)
    k_c, v_c = got[:, :kvh], got[:, kvh:]
    key_pos = jnp.arange(page_table.shape[1] * ps)
    mask = key_pos[None, None, None, None, :] <= qpos[:, None, None, :, None]
    att = jnp.einsum("btkgd,bksd->bkgts", q.reshape(r, t, kvh, g, hd),
                     k_c) / math.sqrt(hd)
    att = jnp.where(mask, att, _NEG_INF)
    w = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bksd->btkgd", w, v_c).reshape(r, t, nh, hd)
    return out if out.dtype == q.dtype else out.astype(q.dtype)


def _grouped_window_xla(q, pool, page_table, pos0, true_len, layer,
                        window: int):
    """The ``jax.numpy`` spelling: only the ``ceil((window - 1 + T) / ps) +
    1`` pages that can hold a visible key are gathered
    (``window_latent_attention``'s walk), every key/value head against its
    ``G`` query heads, float32 softmax."""
    r, t, nh, hd = q.shape
    kvh, ps = pool.shape[-3] // 2, pool.shape[-2]
    g, nps = nh // kvh, page_table.shape[1]
    wp = min(nps, -(-(window - 1 + t) // ps) + 1)
    first = jnp.maximum(pos0 - (window - 1), 0) // ps           # [R]
    cols = first[:, None] + jnp.arange(wp, dtype=pos0.dtype)[None, :]
    pages = jnp.where(cols < nps, jnp.take_along_axis(
        page_table, jnp.minimum(cols, nps - 1), axis=1), 0)
    got = pool[layer, pages]                        # [R, wp, 2 KVH, ps, D]
    if got.dtype != q.dtype:
        got = got.astype(jnp.promote_types(got.dtype, q.dtype))
    got = jnp.swapaxes(got, 1, 2).reshape(r, 2 * kvh, wp * ps, hd)
    kpos = first[:, None] * ps + jnp.arange(wp * ps, dtype=pos0.dtype)
    live = jnp.where(true_len > 0, pos0 + true_len, 0)
    qpos = jnp.minimum(pos0[:, None] + jnp.arange(t, dtype=pos0.dtype),
                       live[:, None] - 1)
    k3, q3 = kpos[:, None, :], qpos[:, :, None]
    keep = (k3 <= q3) & (k3 > q3 - window)                  # [R, T, S]
    # a page behind the window may be gone and a position past the live
    # ones holds whatever was there: a weight of 0 must meet no NaN
    held = (kpos < live[:, None]) & (kpos > pos0[:, None] - window)
    got = jnp.where(held[:, None, :, None], got, 0)
    k_c, v_c = got[:, :kvh], got[:, kvh:]
    att = jnp.einsum("btkgd,bksd->bkgts", q.reshape(r, t, kvh, g, hd),
                     k_c) / math.sqrt(hd)
    att = jnp.where(keep[:, None, None], att, _NEG_INF)
    w = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bksd->btkgd", w, v_c).reshape(r, t, nh, hd)
    return out if out.dtype == q.dtype else out.astype(q.dtype)


def grouped_paged_attention(q, pool, page_table, pos0, true_len, layer,
                            impl=None, window=None):
    """``ragged_paged_attention`` over a grouped pool ``[L, P, 2 KVH, ps,
    D]``: ``q`` [R, T, NH, D], query heads ``k G .. (k + 1) G - 1`` read
    key/value head ``k``; the same two spellings, picked and counted the
    same way. ``window`` (static; ISSUE 57): a query at ``t`` sees keys ``t
    - window < j <= t``, and a row's table entries behind the window may be
    null (``serving.paged_cache.WindowSpace`` keeps only the window's
    pages). Both spellings then start a row's walk at the page (the kernel:
    the block) of its first query's oldest visible key and end it at its
    last live page; what lies between the walk's start and the window's edge
    is masked, and so is a null page's content."""
    from ..profiler import metrics

    impl = resolve_impl(impl)
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown paged attention impl {impl!r}")
    metrics.registry().counter("serving/attn_calls{path=%s}" % impl).add(1)
    if impl == "pallas":
        return _grouped_attention_pallas(q, pool, page_table, pos0, true_len,
                                         layer, window)
    if window is not None:
        return _grouped_window_xla(q, pool, page_table, pos0, true_len, layer,
                                   int(window))
    qpos = pos0[:, None] + jnp.arange(q.shape[1], dtype=pos0.dtype)
    return _grouped_gather_attend(q, pool, page_table, qpos, layer)


def _grouped_kernel(pt_ref, pos0_ref, tl_ref, layer_ref, q_ref, kv_hbm, o_ref,
                    buf, sem, slot_ref, m_ref, l_ref, acc_ref, *, group: int,
                    window=None):
    """Grid (r,): row ``r``, ``_ragged_kernel``'s walk (the pool in HBM, the
    KV axis a loop over blocks of ``bp`` pages whose trip count is the row's
    own, two buffers, the next block or the next row's first in flight). A
    page is one copy, K's heads and V's together. ``q_ref`` ``[1, KVH, G Tp,
    D]``: a key/value head's ``G`` query heads of ``Tp`` queries each, row
    ``j Tp + i`` query ``i`` of head ``j``, one left operand of its two
    products a block. Under a ``window`` the walk starts at the block of the
    row's oldest visible key: row ``r`` visits blocks ``b0(r) .. b0(r) +
    nblk(r) - 1`` (a decode row under a window of 512 and blocks of 256
    positions two or three, whatever its context), and a score is kept where
    ``j <= t``, ``j > t - window`` and ``j`` is live; a query whose first
    blocks hold nothing it sees accumulates under the mask's constant until
    its first visible key arrives, whose rescale (``exp(-1e9 - s)``, 0)
    wipes that. With no window ``b0`` is the number 0 and no operation is
    traced for it or for the lower mask."""
    _, bp, kv2, ps, hd = buf.shape
    kvh = kv2 // 2
    m = q_ref.shape[2]
    tp = m // group
    nps = pt_ref.shape[1]
    bt = bp * ps
    r = pl.program_id(0)
    last_row = r + 1 == pl.num_programs(0)
    layer = layer_ref[0]

    def kv_len(row):
        n = jnp.minimum(pos0_ref[row] + tl_ref[row], nps * ps)
        return jnp.where(tl_ref[row] > 0, n, 0)

    def first_block(row):
        if window is None:
            return 0
        oldest = jnp.maximum(pos0_ref[row] - (window - 1), 0)
        return jnp.minimum(oldest // bt,
                           jnp.maximum(pl.cdiv(kv_len(row), bt) - 1, 0))

    def copies(row, blk, slot, act):
        first = blk * bp
        count = jnp.minimum(pl.cdiv(kv_len(row), ps) - first, bp)

        def one(i, carry):
            act(pltpu.make_async_copy(
                kv_hbm.at[layer, pt_ref[row, first + i]], buf.at[slot, i],
                sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, count, one, 0)

    start = lambda c: c.start()
    wait = lambda c: c.wait()
    n_live = kv_len(r)
    b0 = first_block(r)
    nblk = pl.cdiv(n_live, bt)                  # the blocks the row visits
    if window is not None:
        nblk = nblk - b0

    @pl.when(r == 0)
    def _first():
        slot_ref[0] = 0
        copies(r, b0, 0, start)

    slot0 = slot_ref[0]
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    # the query of each row of a head's operand
    qi = jax.lax.broadcasted_iota(jnp.int32, (group, tp, bt), 1).reshape(
        1, m, bt)
    qpos = pos0_ref[r] + qi

    def block(j, carry):
        b = j if window is None else b0 + j
        slot = (slot0 + j) % 2

        @pl.when(j + 1 < nblk)
        def _next_block():
            copies(r, b + 1, 1 - slot, start)

        @pl.when(jnp.logical_and(j + 1 == nblk, jnp.logical_not(last_row)))
        def _next_row():
            copies(r + 1, first_block(r + 1), 1 - slot, start)

        copies(r, b, slot, wait)
        src = buf.at[slot]
        left = n_live - b * bt              # live positions of this block
        head = lambda h: src[:, h].reshape(bt, hd)          # noqa: E731
        s = jnp.stack([_dot(q_ref[0, h], head(h), (((1,), (1,)), ((), ())))
                       for h in range(kvh)]) / math.sqrt(hd)  # [KVH, M, bt]
        kpos = b * bt + jax.lax.broadcasted_iota(jnp.int32, (1, m, bt), 2)
        # a query past the row's live tokens (a pad) sees what the last does
        seen = jnp.minimum(qpos, n_live - 1)
        keep = kpos <= seen
        if window is not None:
            keep = jnp.logical_and(keep, kpos > seen - window)
        s = jnp.where(keep, s, _NEG_INF)
        m_prev = m_ref[:]                               # [KVH, M, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = corr * l_ref[:] + jnp.sum(p, axis=2, keepdims=True)
        m_ref[:] = m_new
        # rows past the live ones hold what an earlier block left there, or
        # nothing at all: 0 x NaN must not reach the accumulator
        dead = jax.lax.broadcasted_iota(jnp.int32, (bt, hd), 0) >= left
        pv = []
        for h in range(kvh):
            v = head(kvh + h)
            pv.append(_dot(p[h].astype(v.dtype),
                           jnp.where(dead, jnp.zeros_like(v), v),
                           (((1,), (0,)), ((), ()))))
        acc_ref[:] = corr * acc_ref[:] + jnp.stack(pv)
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)

    @pl.when(jnp.logical_and(nblk == 0, jnp.logical_not(last_row)))
    def _next_row_of_an_empty_one():
        copies(r + 1, first_block(r + 1), slot0, start)

    slot_ref[0] = (slot0 + nblk) % 2
    l = l_ref[:]
    o_ref[0] = acc_ref[:] / jnp.where(l == 0.0, 1.0, l)


def _grouped_attention_pallas(q, pool, page_table, pos0, true_len, layer,
                              window=None):
    r, t, nh, hd = q.shape
    kv2, ps = pool.shape[-3], pool.shape[-2]
    kvh = kv2 // 2
    g = nh // kvh
    nps = page_table.shape[1]
    bp = kv_block_pages(ps, nps)
    kv_dtype = jnp.promote_types(pool.dtype, q.dtype)
    if pool.dtype != kv_dtype:
        raise NotImplementedError(
            f"grouped pages of {pool.dtype} under queries of {q.dtype}: the "
            "kernel multiplies pages as they lie")
    rows = 8 * _rows_per_word(kv_dtype)
    tp = -(-t // rows) * rows
    # [R, KVH, G Tp, D]: a key/value head's query heads one after the other
    qk = jnp.transpose(q.astype(kv_dtype).reshape(r, t, kvh, g, hd),
                       (0, 2, 3, 1, 4))
    if tp != t:
        qk = jnp.pad(qk, ((0, 0),) * 3 + ((0, tp - t), (0, 0)))
    m = g * tp
    block = pl.BlockSpec((1, kvh, m, hd),
                         lambda i, pt, p0, tl, ly: (i, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, group=g,
                          window=None if window is None else int(window)),
        name="grouped_paged_attn" if window is None else "grouped_window_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(r,),
            in_specs=[block, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((2, bp, kv2, ps, hd), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((kvh, m, 1), jnp.float32),
                            pltpu.VMEM((kvh, m, 1), jnp.float32),
                            pltpu.VMEM((kvh, m, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((r, kvh, m, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(page_table, pos0, true_len,
      jnp.asarray(layer, jnp.int32).reshape(1),
      qk.reshape(r, kvh, m, hd), pool)
    out = out.reshape(r, kvh, g, tp, hd)[:, :, :, :t]
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(q.shape).astype(
        q.dtype)
