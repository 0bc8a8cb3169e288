"""Latent pools (ISSUE 37): one vector a token a layer in place of K and V.

A latent-attention model (MLA, arXiv:2405.04434) caches ``(c_kv, k_rope)``,
one row of ``C + R`` numbers a token a layer that every head shares;
attention runs in the absorbed form (the queries carried into the latent
space, the values carried out of it afterwards), so a pool has no head axis.
It is ``[L, P, W, ps]`` (``serving.paged_cache.LatentPools``), a page's
tokens along the *last* axis, which is how the reads want it (keys on the
lanes of their products). A write of one token's row is then a column of its
page, and XLA:TPU re-lays the whole pool for a scatter of columns and back
(three copies of the 0.93 GB latent pool a tick, 9 ms of 44 on a v5e, with
either order of the two axes; PERF.md section 6, PR 37). So the writes go a
page at a time (``latent_scatter``): the pages a tick touches are read,
given their new columns and written back whole, which is the pool's own
layout.

The functions here are the write and the read sides of such pools: the same
walk over a row's own pages as ``ops/paged_attention``'s ragged kernel,
other contents. Every shape is fixed, every trip count is the rows' own. All
are ``jax.numpy`` but the full layers' attentions, which have the same two
spellings behind one entry point as ``ragged_paged_attention`` (ISSUE 39),
picked where the program is traced, by platform and shapes
(``latent_attention_path``): the XLA walk, whose float32 score blocks
``[256, 128, 2048]`` go through HBM five times a block (on a v5e 6.8 ms for
one layer's chunk of 256 queries with 8,960 positions behind it, 2.6 ms for
twelve decode rows of which six are live at 13-21 k: every row walks as far
as the longest), and the Pallas kernel at the end of this file, a body over
``paged_attention._walk_pages`` (a page ``[W, ps]`` is what ``q @ page``
wants as its right-hand side, so the kernel fetches pages by id as they lie
and nothing is re-laid) whose scores stay in VMEM (``selected_latent_attn``:
4.2 ms and 0.44 ms; 16.4 -> 8.6 ms of the dots3 cell's tick; PERF.md section
6, PR 39). ``latent_attention`` (ISSUE 40) is the same pair without a
selection: every visible position of the row, DeepSeek-V2's dense MLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (_NEG_INF, _dot, _einsum_f32, _interpret,
                              _rows_per_word, _walk_pages, resolve_impl)

__all__ = ["latent_scatter", "index_scores", "select_topk",
           "select_threshold", "selection_mask", "selected_latent_attention",
           "latent_attention", "window_latent_attention"]

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
      ``selected_latent_attn``. Grid (row, tile of queries), a tile's
      pages by ``paged_attention._walk_pages`` only as far as its own last
      query sees; scores, mask and softmax never leave VMEM. The same
      calls: 0.59 / 4.2 / 7.3 ms and 0.44 ms, the products at about 160
      TFLOP/s of the chip's 197 behind a long context;
      ``mla.attn_ms_per_tick`` of ``serve-dots3-longdoc-backlog`` 16.4 ->
      8.6 ms (two layers).
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
    (``latent_attn``: the same walk, an online softmax in VMEM, nothing of
    extent heads x keys in HBM) with the
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
    """Grid (r, j): a step is tile ``j`` of ``tq`` consecutive queries of
    row ``r``, then the row's next tile or the next row's first; it sees as
    far as its last real query does (``visible``), so a tile with no real
    query (a free slot's row, the pad tiles of a short chunk) costs the grid
    step alone. A page is one copy, side by side with the block's others
    along the lanes of a buffer ``[W, bp * ps]`` (``_walk_pages``).

    The body: every head of the tile's queries is a row of its products
    (``tq * NH`` rows of ``W``). A block's scores ``[tq * NH, bp * ps]``
    live in VMEM, in float32; the selection's mask is made once a query from
    its ``keys``, ``thr`` and last taken tie, and laid over its heads;
    running maximum, sum and accumulator are float32, the weights meet the
    latents again in the pool's type. ``keys_ref`` None
    (``_dense_latent_kernel``): no selection, the causal mask alone."""
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

    def page_copies(page, slot, i):
        return [(pool_hbm.at[layer, page],
                 buf.at[slot, :, pl.ds(pl.multiple_of(i * ps, ps), ps)])]

    def tile(n_vis):
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q = q_ref[0].reshape(rows, width)
        if keys_ref is not None:
            thr, cut = thr_ref[0], cut_ref[0]                   # [tq, 1]
        qpos = pos0_ref[r] + j * tq + jax.lax.broadcasted_iota(
            jnp.int32, (tq, bt), 0)
        last = jnp.minimum(qpos, n_vis - 1)

        def block(b, slot, left):
            lat = buf.at[slot]

            @pl.when(left < bt)
            def _dead():
                # what lies past the tile's last position is whatever the
                # buffer or the page held: zeros, so that a weight of 0
                # cannot meet a NaN
                at = jax.lax.broadcasted_iota(jnp.int32, (width, bt), 1)
                lat[...] = jnp.where(at < left, lat[...],
                                     jnp.zeros_like(lat))

            s_ref[...] = _dot(q, lat[...].astype(q.dtype),
                              (((1,), (0,)), ((), ())))         # [rows, bt]
            kpos = b * bt + jax.lax.broadcasted_iota(jnp.int32, (tq, bt), 1)
            keep = kpos <= last
            if keys_ref is not None:
                mine = keys_ref[0, :, pl.ds(pl.multiple_of(b * bt, bt), bt)]
                keep = keep & ((mine > thr)
                               | ((mine == thr) & (kpos <= cut)))
            bias = jnp.where(keep, 0.0, _NEG_INF)               # [tq, bt]
            for i in range(tq):         # a query's heads share its mask
                at = slice(i * nh, (i + 1) * nh)
                s = s_ref[at, :] * scale + bias[i:i + 1, :]
                m_prev = m_ref[at, :]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                # where nothing was kept yet m is the mask's constant and
                # the weights are 1: the first kept score's ``corr`` is
                # exactly 0
                p = jnp.exp(s - m_new)
                corr_ref[at, :] = jnp.exp(m_prev - m_new)
                l_ref[at, :] = corr_ref[at, :] * l_ref[at, :] \
                    + jnp.sum(p, axis=1, keepdims=True)
                m_ref[at, :] = m_new
                p_ref[at, :] = p.astype(p_ref.dtype)
            acc_ref[...] = corr_ref[...] * acc_ref[...] + _dot(
                p_ref[...], lat[:c_width, :].astype(q.dtype),
                (((1,), (1,)), ((), ())))                       # [rows, C]

        return block

    _walk_pages((r, j), lambda: (next_r, next_j), last_step, live=visible,
                pt_ref=pt_ref, page_copies=page_copies, bp=bp, ps=ps,
                sem=sem, slot_ref=slot_ref, begin=tile)
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

