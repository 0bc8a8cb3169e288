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
  metadata. The loop is ``_walk_pages`` (below: the one account of how a
  block's live pages are fetched), so **a page past a row's last live
  position is never read** (tests/test_page_walk.py;
  tests/test_ragged_kernel.py fills them with NaN) and a row of
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

Three kernels walk a row's pages and all three through ``_walk_pages``
(ISSUE 60): ``_ragged_kernel`` here, ``_grouped_kernel`` at the end of this
file (fewer key/value heads than query heads, ISSUE 54; under a sliding
window, ISSUE 57) and ``_latent_kernel`` in ``ops/latent_attention.py`` (one
vector a token a layer in place of K and V, ISSUE 37). A new attention is a
pool method in ``serving/paged_cache.py``, a body over the walk and a path
function where the program is traced.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_paged_attention", "paged_kv_scatter",
           "grouped_paged_attention", "grouped_kv_scatter"]

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


def _einsum_f32(spec: str, a, b):
    """``einsum`` accumulated and returned in float32. The CPU's dot has
    no 16-bit x 16-bit -> float32 form for every contraction, so there the
    operands are widened first (the same numbers: a product of two bf16
    values is exact in float32)."""
    if a.dtype.itemsize == 2 and _interpret():
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


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


def _walk_pages(step, following, last, *, live, pt_ref, page_copies,
                bp: int, ps: int, sem, slot_ref, begin, first_block=None):
    """The page walk of a serving kernel: called once in a kernel's body, it
    runs one grid step's loop over the blocks of ``bp`` pages of ``ps``
    positions that the step sees, every block's *live* pages fetched from
    HBM by page id. This is the one account of it; a kernel says what its
    step, its page copy and its body are.

    A step walks blocks ``first_block(step) .. ceil(live(step) / (bp ps)) -
    1``; of a block it fetches ``min(ceil(live / ps) - b bp, bp)`` pages, so
    a page past the step's last live position is never read, and a step
    that sees nothing costs its grid step and no copy. Two buffers take
    turns: while ``body`` multiplies a block out of one, the step's next
    block, or after its last block the next step's first, is in flight into
    the other; the copies of a block are started and waited for over the
    same descriptors, on ``sem[slot]``. The grid's first step starts its own
    first block; a step that walks no block hands the one buffer in turn on
    to the step after it. Whose turn it is goes from grid step to grid step
    in ``slot_ref`` (SMEM): **every grid axis must be sequential**
    (``dimension_semantics`` all ``"arbitrary"``), in the order ``following``
    says.

    step          tuple of int32  this step, its first member its row of
                                  ``pt_ref`` (the grid's ``program_id``s;
                                  the first step is the one of all zeros)
    following     () -> tuple     the step after this one (traced where a
                                  copy for it is started)
    last          bool scalar     this is the grid's last step
    live          (*step) -> n    positions the step sees, 0 for none
    first_block   (*step) -> b    the first block it walks; None: the number
                                  0, and nothing is traced for it
    page_copies   (page, slot, i) -> [(src, dst)]: one page's copies into
                                  place ``i`` of buffer ``slot``
    sem, slot_ref                 DMA semaphores ``(2,)``, SMEM ``(1,)`` int32
    begin         (n_live) -> body: run once, before the loop (a kernel
                                  resets its accumulators there);
                                  ``body(b, slot, left)`` multiplies block
                                  ``b``, fetched into buffer ``slot``, of
                                  which ``left`` positions from its start
                                  are live (more than a block's: all)
    """
    bt = bp * ps

    def copies(at, blk, slot, act):
        """``act`` (start or wait) on the copy of every live page of block
        ``blk`` of step ``at`` into buffer ``slot``."""
        first = blk * bp
        count = jnp.minimum(pl.cdiv(live(*at), ps) - first, bp)

        def one(i, carry):
            page = pt_ref[at[0], first + i]
            for src, dst in page_copies(page, slot, i):
                act(pltpu.make_async_copy(src, dst, sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, count, one, 0)

    start = lambda c: c.start()
    wait = lambda c: c.wait()

    def next_first():
        """The step after this one and the first block it walks."""
        nxt = following()
        return nxt, 0 if first_block is None else first_block(*nxt)

    n_live = live(*step)
    b0 = 0 if first_block is None else first_block(*step)
    nblk = pl.cdiv(n_live, bt)                  # the blocks the step visits
    if first_block is not None:
        nblk = nblk - b0

    @pl.when(functools.reduce(jnp.logical_and, [s == 0 for s in step]))
    def _first():
        slot_ref[0] = 0
        copies(step, b0, 0, start)

    slot0 = slot_ref[0]
    body = begin(n_live)

    def block(j, carry):
        b = j if first_block is None else b0 + j
        slot = (slot0 + j) % 2

        @pl.when(j + 1 < nblk)
        def _next_block():
            copies(step, b + 1, 1 - slot, start)

        @pl.when(jnp.logical_and(j + 1 == nblk, jnp.logical_not(last)))
        def _next_step():
            copies(*next_first(), 1 - slot, start)

        copies(step, b, slot, wait)
        body(b, slot, n_live - b * bt)
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)

    @pl.when(jnp.logical_and(nblk == 0, jnp.logical_not(last)))
    def _next_step_of_an_empty_one():
        copies(*next_first(), slot0, start)

    slot_ref[0] = (slot0 + nblk) % 2


def _ragged_kernel(pt_ref, pos0_ref, tl_ref, layer_ref, q_ref, k_hbm, v_hbm,
                   *rest, t: int, quant: bool, split: bool):
    """Grid (r,): a step is row ``r``, which sees its ``kv_len`` positions;
    a page is two copies, K's stream and V's (``_walk_pages``).

    The body: ``split`` (the heads fill whole tiles) reads every head of q,
    K and V as its own ``[rows, D]`` by a strided load and runs two plain
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
    _, bp, ps, nh, hd = kbuf.shape
    nps = pt_ref.shape[1]
    bt = bp * ps
    r = pl.program_id(0)
    last_row = r + 1 == pl.num_programs(0)
    layer = layer_ref[0]

    def kv_len(row):
        n = jnp.minimum(pos0_ref[row] + tl_ref[row], nps * ps)
        return jnp.where(tl_ref[row] > 0, n, 0)

    def page_copies(page, slot, i):
        return [(hbm.at[layer, page], buf.at[slot, i])
                for hbm, buf in ((k_hbm, kbuf), (v_hbm, vbuf))]

    def row(n_live):
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

        def block(b, slot, left):
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
            qpos = pos0_ref[r] + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
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

        return block

    _walk_pages((r,), lambda: (r + 1,), last_row, live=kv_len, pt_ref=pt_ref,
                page_copies=page_copies, bp=bp, ps=ps, sem=sem,
                slot_ref=slot_ref, begin=row)
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
# product: their queries lie end to end on the rows of its left operand,
# which is padded to whole tiles once, behind the last (ISSUE 61).
# Writes go a page at a time (``latent_attention.latent_scatter``'s way: a
# token's row is one sublane of a tile, and XLA:TPU re-lays a whole pool
# around a scatter of such rows): the pages a tick touches are read, given
# their new rows and written back whole.


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
    same way. The kernel multiplies a key/value head by its ``G`` heads'
    queries at once (``_grouped_operand``: ``G T`` rows padded once to whole
    tiles, whatever ``G`` and ``T`` are) and counts the operand it was given
    where it is traced (``serving/grouped_attn_operand{queries=,rows=}``).
    ``window`` (static; ISSUE 57): a query at ``t`` sees keys ``t
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
                    buf, sem, slot_ref, m_ref, l_ref, acc_ref, *, t: int,
                    window=None):
    """Grid (r,): a step is row ``r``, which sees its ``kv_len`` positions;
    a page is one copy, K's heads and V's together (``_walk_pages``). The
    body: ``q_ref`` ``[1, KVH, M, D]`` (``_grouped_operand``) is a key/value
    head's ``G`` query heads of ``t`` queries each laid end to end, row ``j
    t + i`` query ``i`` of head ``j``, and padded once behind the last to
    whole tiles (``M >= G t``): one left operand of its two products a
    block. A pad row ``k >= G t`` is masked as query ``k mod t``, so it sees
    keys as a live row does and meets no block of scores all masked; the
    wrapper cuts it. Under a ``window`` the walk starts at the block of the
    row's oldest visible key: row ``r`` visits
    blocks ``b0(r) .. b0(r) + nblk(r) - 1`` (a decode row under a window of
    512 and blocks of 256 positions two or three, whatever its context), and
    a score is kept where ``j <= t``, ``j > t - window`` and ``j`` is live; a
    query whose first blocks hold nothing it sees accumulates under the
    mask's constant until its first visible key arrives, whose rescale
    (``exp(-1e9 - s)``, 0) wipes that. With no window no operation is traced
    for the first block or for the lower mask."""
    _, bp, kv2, ps, hd = buf.shape
    kvh = kv2 // 2
    m = q_ref.shape[2]
    nps = pt_ref.shape[1]
    bt = bp * ps
    r = pl.program_id(0)
    last_row = r + 1 == pl.num_programs(0)
    layer = layer_ref[0]

    def kv_len(row):
        n = jnp.minimum(pos0_ref[row] + tl_ref[row], nps * ps)
        return jnp.where(tl_ref[row] > 0, n, 0)

    def first_block(row):
        oldest = jnp.maximum(pos0_ref[row] - (window - 1), 0)
        return jnp.minimum(oldest // bt,
                           jnp.maximum(pl.cdiv(kv_len(row), bt) - 1, 0))

    def row(n_live):
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # the query of each row of a head's operand: row k is query k mod t
        qpos = pos0_ref[r] + jax.lax.broadcasted_iota(
            jnp.int32, (1, m, bt), 1) % t

        def block(b, slot, left):
            src = buf.at[slot]
            head = lambda h: src[:, h].reshape(bt, hd)          # noqa: E731
            s = jnp.stack([
                _dot(q_ref[0, h], head(h), (((1,), (1,)), ((), ())))
                for h in range(kvh)]) / math.sqrt(hd)       # [KVH, M, bt]
            kpos = b * bt + jax.lax.broadcasted_iota(jnp.int32, (1, m, bt), 2)
            # a query past the row's live tokens (a pad) sees what the last
            # does
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
            # rows past the live ones hold what an earlier block left there,
            # or nothing at all: 0 x NaN must not reach the accumulator
            dead = jax.lax.broadcasted_iota(jnp.int32, (bt, hd), 0) >= left
            pv = []
            for h in range(kvh):
                v = head(kvh + h)
                pv.append(_dot(p[h].astype(v.dtype),
                               jnp.where(dead, jnp.zeros_like(v), v),
                               (((1,), (0,)), ((), ()))))
            acc_ref[:] = corr * acc_ref[:] + jnp.stack(pv)

        return block

    _walk_pages(
        (r,), lambda: (r + 1,), last_row, live=kv_len, pt_ref=pt_ref,
        page_copies=lambda page, slot, i: [(kv_hbm.at[layer, page],
                                            buf.at[slot, i])],
        bp=bp, ps=ps, sem=sem, slot_ref=slot_ref, begin=row,
        first_block=None if window is None else first_block)
    l = l_ref[:]
    o_ref[0] = acc_ref[:] / jnp.where(l == 0.0, 1.0, l)


def _grouped_operand(q, kvh: int, dtype):
    """``q`` [R, T, NH, D] as the kernel's left operands ``[R, KVH, M, D]``:
    a key/value head's ``G`` query heads' ``T`` queries one after the other
    (row ``j T + i`` query ``i`` of head ``j``), padded with zeros **once**,
    behind the last, to whole sublane tiles of ``dtype``: a decode row's 5,
    6 or 9 bf16 queries are one tile of 16, and a chunk's piece whose ``G T``
    fills its tiles is not padded at all."""
    r, t, nh, hd = q.shape
    g = nh // kvh
    pad = -(g * t) % (8 * _rows_per_word(dtype))
    qk = jnp.transpose(q.astype(dtype).reshape(r, t, kvh, g, hd),
                       (0, 2, 3, 1, 4)).reshape(r, kvh, g * t, hd)
    return jnp.pad(qk, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else qk


def _grouped_attention_pallas(q, pool, page_table, pos0, true_len, layer,
                              window=None):
    from ..profiler import metrics

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
    qk = _grouped_operand(q, kvh, kv_dtype)
    m = qk.shape[2]
    metrics.registry().counter(
        "serving/grouped_attn_operand{queries=%d,rows=%d}" % (g * t, m)).add(1)
    block = pl.BlockSpec((1, kvh, m, hd),
                         lambda i, pt, p0, tl, ly: (i, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, t=t,
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
      jnp.asarray(layer, jnp.int32).reshape(1), qk, pool)
    out = out[:, :, :g * t].reshape(r, kvh, g, t, hd)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(q.shape).astype(
        q.dtype)
