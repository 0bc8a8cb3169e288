"""Grouped-query attention over K/V pages (``paged_cache.GroupedPools``,
``ops/paged_attention.grouped_paged_attention`` and ``grouped_kv_scatter``):
fewer key/value heads than query heads, a page's positions on the sublanes,
no padded head. Both spellings (the kernel interpreted) against dense
attention; the write a page at a time; and that a multi-head spec builds the
pool it built before."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.profiler import registry
from paddle_tpu.serving.paged_cache import (GroupedPools, Pools, StatePools,
                                            page_pool)

PS = 8


def _dense(q, k, v, pos0, true_len):
    """Plain causal attention a row: q [T, NH, D] at positions ``pos0 +
    i`` over k, v [S, KVH, D], head ``j`` reading key/value head ``j //
    (NH / KVH)``; float64."""
    t, nh, d = q.shape
    per = nh // k.shape[1]
    out = np.zeros((t, nh, d))
    for i in range(min(t, true_len)):
        seen = pos0 + i + 1
        for j in range(nh):
            s = k[:seen, j // per] @ q[i, j] / np.sqrt(d)
            w = np.exp(s - s.max())
            out[i, j] = (w / w.sum()) @ v[:seen, j // per]
    return out


def _filled(seed, layers, rows, nps, kvh, d, lens, dtype=jnp.float32,
            named=False):
    """A grouped pool whose rows hold ``lens`` positions each on pages of
    their own (shuffled ids), NaN on every page no row reaches. The table
    names a row's live pages alone (null entries behind them) or, ``named``,
    every page of the row."""
    rng = np.random.default_rng(seed)
    n_pages = 1 + rows * nps
    ids = rng.permutation(np.arange(1, n_pages)).reshape(rows, nps)
    pool = np.full((layers, n_pages, 2 * kvh, PS, d), np.nan, np.float32)
    pool[:, 0] = 0.0
    ks, vs = [], []
    for r, n in enumerate(lens):
        k = rng.standard_normal((layers, n, kvh, d)).astype(np.float32)
        v = rng.standard_normal((layers, n, kvh, d)).astype(np.float32)
        ks.append(k), vs.append(v)
        for p in range(-(-n // PS)):
            got = slice(p * PS, min((p + 1) * PS, n))
            m = got.stop - got.start
            pool[:, ids[r, p], :kvh, :m] = np.swapaxes(k[:, got], 1, 2)
            pool[:, ids[r, p], kvh:, :m] = np.swapaxes(v[:, got], 1, 2)
            pool[:, ids[r, p], :, m:] = 0.0 if m < PS else pool[
                :, ids[r, p], :, m:]
    table = ids if named else np.where(
        np.arange(nps)[None, :] < -(-np.asarray(lens)[:, None] // PS), ids, 0)
    return jnp.asarray(pool, dtype), jnp.asarray(table, jnp.int32), ks, vs


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("heads,kvh,d", [(20, 4, 32), (4, 2, 16)])
def test_decode_rows_against_dense_attention(impl, heads, kvh, d):
    lens = [37, 0, 8, 63]
    pool, table, ks, vs = _filled(0, 2, 4, 8, kvh, d, lens)
    rng = np.random.default_rng(1)
    q = rng.standard_normal((4, 1, heads, d)).astype(np.float32)
    pos0 = jnp.asarray([n - 1 if n else 0 for n in lens], jnp.int32)
    tl = jnp.asarray([1 if n else 0 for n in lens], jnp.int32)
    if impl == "xla":
        # the gather reads whole tables: no NaN behind the mask's zeros
        pool = jnp.nan_to_num(pool)
    got = pa.grouped_paged_attention(jnp.asarray(q), pool, table, pos0, tl,
                                     1, impl=impl)
    assert got.shape == q.shape
    for r, n in enumerate(lens):
        if n:
            want = _dense(q[r], ks[r][1], vs[r][1], n - 1, 1)
            np.testing.assert_allclose(got[r], want, atol=2e-5, rtol=2e-5)
    assert bool(jnp.all(jnp.isfinite(got)))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("heads,kvh,d", [(20, 4, 32), (4, 2, 16)])
def test_chunk_rows_against_dense_attention(impl, heads, kvh, d):
    t = 16
    lens = [40, 21]                 # a chunk of 16 after 24, one of 13 after 8
    pool, table, ks, vs = _filled(2, 1, 2, 8, kvh, d, lens)
    q = np.random.default_rng(3).standard_normal(
        (2, t, heads, d)).astype(np.float32)
    pos0, tl = jnp.asarray([24, 8], jnp.int32), jnp.asarray([16, 13],
                                                            jnp.int32)
    if impl == "xla":
        pool = jnp.nan_to_num(pool)
    got = pa.grouped_paged_attention(jnp.asarray(q), pool, table, pos0, tl,
                                     0, impl=impl)
    for r in range(2):
        n = int(tl[r])
        want = _dense(q[r], ks[r][0], vs[r][0], int(pos0[r]), n)
        np.testing.assert_allclose(got[r, :n], want[:n], atol=2e-5,
                                   rtol=2e-5)


def _rows_of(lens, t):
    """Decode rows (``t`` 1) or chunk rows whose last ``min(n, t)`` positions
    are the queries, for rows of ``lens`` positions."""
    tl = np.minimum(np.asarray(lens), t)
    return (jnp.asarray(np.asarray(lens) - tl, jnp.int32),
            jnp.asarray(tl, jnp.int32))


@pytest.mark.parametrize("window", [None, 21], ids=["full", "window"])
def test_nothing_past_a_rows_last_position_is_read(window, t=8):
    """The mechanism (``_walk_pages``) under this kernel's body, rows of up
    to eight queries (a row of one position is a decode row): every page
    past each row's last live one, what lies behind the last position inside
    that page and, under a window, every page before the block of the row's
    oldest visible key, filled with NaN and +inf and *named by the table*,
    leaves the kernel's output as it was bit for bit, and finite. The
    capacity-wide spelling cannot pass this (0 x NaN; the windowed one
    gathers the window's pages alone and zeroes what it does not hold)."""
    kvh, d, nps = 2, 16, 12
    lens = [37, 0, 8, 96, 63, 1]
    pool, ids, _, _ = _filled(8, 1, len(lens), nps, kvh, d, lens, named=True)
    pool, ids = np.array(jnp.nan_to_num(pool)), np.asarray(ids)
    table = np.where(np.arange(nps)[None, :] < -(-np.asarray(lens)[:, None]
                                                 // PS), ids, 0)
    pos0, tl = _rows_of(lens, t)
    q = jnp.asarray(np.random.default_rng(9).standard_normal(
        (len(lens), t, 12, d)), jnp.float32)
    attend = lambda pool_, table_, impl: pa.grouped_paged_attention(  # noqa
        q, jnp.asarray(pool_), jnp.asarray(table_, jnp.int32), pos0, tl, 0,
        impl=impl, window=window)
    clean = attend(pool, table, "pallas")
    bad = np.array([np.nan, np.inf], np.float32)
    dirty, bt = pool.copy(), pa.kv_block_pages(PS, nps) * PS
    for r, n in enumerate(lens):
        dirty[0, ids[r, -(-n // PS):]] = bad[r % 2]
        if n % PS:
            dirty[0, ids[r, n // PS], :, n % PS:] = bad[(r + 1) % 2]
        if window is not None and n:
            oldest = max(int(pos0[r]) - (window - 1), 0)
            dirty[0, ids[r, :oldest // bt * (bt // PS)]] = bad[r % 2]
    got = attend(dirty, ids, "pallas")
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    real = np.arange(t)[None, :] < np.asarray(tl)[:, None]
    spelled = np.asarray(attend(dirty, ids, "xla"))[real]
    assert np.isfinite(spelled).all() == (window is not None)


@pytest.mark.parametrize("window", [None, 21], ids=["full", "window"])
def test_a_row_of_no_tokens_costs_its_neighbours_nothing(window):
    """An empty row (the first, the last, two in a row) walks no block and
    hands the buffer in turn on: the rows around it read what the spelling
    reads, and it gets zeros."""
    kvh, d, lens = 2, 16, [0, 37, 0, 0, 63, 0]
    pool, table, _, _ = _filled(10, 1, len(lens), 8, kvh, d, lens)
    pos0, tl = _rows_of(lens, 1)
    q = jnp.asarray(np.random.default_rng(11).standard_normal(
        (len(lens), 1, 12, d)), jnp.float32)
    got = pa.grouped_paged_attention(q, pool, table, pos0, tl, 0,
                                     impl="pallas", window=window)
    want = pa.grouped_paged_attention(q, jnp.nan_to_num(pool), table, pos0,
                                      tl, 0, impl="xla", window=window)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=2e-5)
    assert not np.asarray(got)[~live].any()


def test_bf16_pages_meet_the_product_as_they_lie():
    lens = [29, 50]
    pool, table, ks, vs = _filled(4, 1, 2, 8, 4, 32, lens, jnp.bfloat16)
    q = jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, 1, 20, 32)), jnp.bfloat16)
    pos0 = jnp.asarray([28, 49], jnp.int32)
    tl = jnp.asarray([1, 1], jnp.int32)
    a = pa.grouped_paged_attention(q, jnp.nan_to_num(pool), table, pos0, tl,
                                   0, impl="xla")
    b = pa.grouped_paged_attention(q, pool, table, pos0, tl, 0,
                                   impl="pallas")
    assert b.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=3e-2)


def test_the_write_goes_a_page_at_a_time_and_touches_no_other():
    kvh, d = 2, 16
    pools = GroupedPools.zeros(2, 6, PS, kvh, d, jnp.float32)
    pools = GroupedPools(pools.kv + 7.0)
    rng = np.random.default_rng(6)
    k = rng.standard_normal((5, 1, kvh, d)).astype(np.float32)
    v = rng.standard_normal((5, 1, kvh, d)).astype(np.float32)
    page = jnp.asarray([3, 3, 5, 0, 1], jnp.int32)      # one token writes
    off = jnp.asarray([2, 3, 0, 4, 7], jnp.int32)       # nothing (page 0)
    touched = jnp.asarray([3, 5, 1, 0, 0, 3], jnp.int32)
    new = pools.scatter(1, page, off, jnp.asarray(k), jnp.asarray(v),
                        touched)
    np.testing.assert_array_equal(new.kv[0], pools.kv[0])
    for t, (p, o) in enumerate(zip([3, 3, 5, None, 1], [2, 3, 0, 4, 7])):
        if p is None:
            continue
        np.testing.assert_array_equal(new.kv[1, p, :kvh, o], k[t, 0])
        np.testing.assert_array_equal(new.kv[1, p, kvh:, o], v[t, 0])
    # every other position of the touched pages, and every other page, is as
    # it was (the token that writes nothing wrote to the null page)
    moved = np.asarray(new.kv[1] != pools.kv[1])
    assert moved.sum() == 5 * 2 * kvh * d and moved[0, :, 4].all()
    np.testing.assert_array_equal(new.kv[1, jnp.asarray([2, 4])], 7.0)
    # without the list every token's own page is read and written
    same = pools.scatter(1, page, off, jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_array_equal(
        np.asarray(same.kv[:, 1:]), np.asarray(new.kv[:, 1:]))
    ks, vs = new.rows_of(1, jnp.asarray([3]))
    np.testing.assert_array_equal(ks[2], k[0, 0])
    np.testing.assert_array_equal(vs[3], v[1, 0])


def test_a_multi_head_spec_builds_the_pool_it_built_before():
    """``StatePools`` of a spec that names no ``key_value_heads`` (Olmo-
    Hybrid's, Ling's) are ``Pools`` of head rows rounded to whole tiles, and
    its ``scatter`` and ``attend`` take the calls they took."""
    spec = {"kind": "state", "layers": 2, "heads": 6, "head_dim": 8,
            "state_layers": 4, "state_heads": 6, "key_dim": 24,
            "value_dim": 48, "conv_width": 576, "conv_taps": 4}
    pools = StatePools.zeros(spec, 10, 4, 3, jnp.bfloat16)
    assert isinstance(pools.kv, Pools) and type(pools) is StatePools
    assert pools.kv.k.shape == (2, 10, 4, 16, 8)
    assert pools.state.shape == (4, 4, 3, 24, 96)
    same = dict(spec, key_value_heads=6)
    assert isinstance(StatePools.zeros(same, 10, 4, 3, jnp.bfloat16).kv,
                      Pools)
    pools = StatePools.zeros(spec, 10, 4, 3, jnp.float32)
    k = jnp.ones((3, 1, 6, 8))
    new = pools.scatter(0, jnp.asarray([1, 2, 0]), jnp.asarray([0, 1, 2]),
                        k, 2 * k)
    assert float(new.kv.v[0, 2, 1, 0, 0]) == 2.0
    lowered = jax.jit(lambda p, q: p.attend(
        1, q, jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.ones((2,), jnp.int32))).lower(pools, jnp.ones((2, 1, 6, 8)))
    assert "grouped" not in lowered.as_text()
    pool = page_pool({"kind": "kv", "layers": 2, "heads": 4, "head_dim": 8},
                     10, 4, 2, 4, 8, jnp.float32, False, False)
    assert isinstance(pool.pools, Pools)


# --------------------------------------------------------------------------
# the kernel's operand (ISSUE 61): a key/value head's G query heads' t
# queries laid end to end and padded once, ragged rows in one call
# --------------------------------------------------------------------------
_BT, _NPS = 32, 12              # blocks of 4 pages, 3 blocks a slot of 96
#: last positions on every edge a walk has: 1, a page's (ps - 1, ps), a
#: block's (31, 32, 33 and 63, 64, 65), the slot's capacity; rows of no
#: tokens first, between live rows and two in a row
_RAGGED = [0, 1, 7, 0, 8, 31, 32, 0, 0, 33, 63, 64, 65, 96, 40]


def _ragged_rows(t, window, seed):
    """The rows of ``_RAGGED`` as decode rows (``t`` 1) or chunk rows whose
    queries are the last ``min(n, t)`` positions, the last row two short of
    ``t`` besides; shuffled page ids, and under a window a null table entry
    for every page wholly behind the row's oldest visible key."""
    lens = np.asarray(_RAGGED)
    tl = np.minimum(lens, t)
    tl[-1] = max(1, t - 2)
    pos0 = lens - tl
    pool, table, _, _ = _filled(seed, 1, len(lens), _NPS, 2, 16, _RAGGED)
    if window is not None:
        behind = np.maximum(pos0 - (window - 1), 0) // PS
        table = jnp.where(np.arange(_NPS)[None, :] < behind[:, None], 0,
                          table)
    return pool, table, jnp.asarray(pos0, jnp.int32), jnp.asarray(
        tl, jnp.int32)


@pytest.mark.parametrize("window", [None, 21], ids=["full", "window"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("t", [1, 3, 16, 32])
@pytest.mark.parametrize("g", [2, 5, 6, 9])
def test_ragged_rows_of_every_group_against_the_spelling(monkeypatch, g, t,
                                                         dtype, window):
    monkeypatch.setattr(pa, "_BLOCK_TOKENS", _BT)
    pool, table, pos0, tl = _ragged_rows(t, window, 100 * g + t)
    q = jnp.asarray(np.random.default_rng(g + t).standard_normal(
        (len(_RAGGED), t, 2 * g, 16)), dtype)
    got = pa.grouped_paged_attention(q, pool.astype(dtype), table, pos0, tl,
                                     0, impl="pallas", window=window)
    want = pa.grouped_paged_attention(
        q, jnp.nan_to_num(pool).astype(dtype), table, pos0, tl, 0,
        impl="xla", window=window)
    assert got.dtype == dtype and bool(jnp.all(jnp.isfinite(got)))
    live = np.arange(t)[None, :] < np.asarray(tl)[:, None]
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    err = np.abs(np.asarray(got, np.float32)
                 - np.asarray(want, np.float32)).max(axis=(2, 3))
    worst = np.unravel_index(np.argmax(np.where(live, err, 0)), err.shape)
    assert err[live].max() <= tol, (
        f"row {worst[0]} ({_RAGGED[worst[0]]} positions), query {worst[1]}: "
        f"{err[worst]:.2e}")
    assert not np.asarray(got, np.float32)[np.asarray(tl) == 0].any()


@pytest.mark.parametrize("g,t,dtype,rows", [
    (5, 1, jnp.bfloat16, 16), (6, 1, jnp.bfloat16, 16),
    (9, 1, jnp.bfloat16, 16), (5, 1, jnp.float32, 8),
    (9, 1, jnp.float32, 16), (5, 3, jnp.bfloat16, 16),
    # a chunk's piece fills its tiles: as many rows as queries, as before
    (5, 64, jnp.bfloat16, 320), (6, 32, jnp.bfloat16, 192),
    (9, 32, jnp.bfloat16, 288), (2, 16, jnp.float32, 32)])
def test_the_operand_is_a_rows_queries_padded_once(g, t, dtype, rows):
    """``ceil(G t / tile) tile`` rows a key/value head, not ``G ceil(t /
    tile) tile``: counted where the wrapper is traced, and the shape of the
    kernel's operand, scratch and result."""
    kvh, d, r = 2, 16, 3
    args = (jax.ShapeDtypeStruct((r, t, kvh * g, d), dtype),
            jax.ShapeDtypeStruct((1, 9, 2 * kvh, PS, d), dtype),
            jax.ShapeDtypeStruct((r, 4), jnp.int32),
            jax.ShapeDtypeStruct((r,), jnp.int32),
            jax.ShapeDtypeStruct((r,), jnp.int32))
    counted = registry().counter(
        "serving/grouped_attn_operand{queries=%d,rows=%d}" % (g * t, rows))
    before = counted.value
    jaxpr = jax.make_jaxpr(lambda *a: pa.grouped_paged_attention(
        *a, 0, impl="pallas"))(*args)
    assert counted.value == before + 1
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.outvars[0].aval.shape == (r, kvh, rows, d)
    assert call.invars[-2].aval.shape == (r, kvh, rows, d)


@pytest.mark.parametrize("window", [None, 21], ids=["full", "window"])
@pytest.mark.parametrize("g,t", [(5, 1), (9, 1), (5, 3)])
def test_what_a_pad_row_holds_reaches_no_live_head(monkeypatch, g, t, window):
    """The operand's rows behind the last query are the wrapper's to fill
    and to cut: NaN and infinities planted there leave every live head's
    result as it was bit for bit."""
    monkeypatch.setattr(pa, "_BLOCK_TOKENS", _BT)
    pool, table, pos0, tl = _ragged_rows(t, window, 7)
    q = jnp.asarray(np.random.default_rng(12).standard_normal(
        (len(_RAGGED), t, 2 * g, 16)), jnp.float32)
    attend = lambda: pa.grouped_paged_attention(            # noqa: E731
        q, pool, table, pos0, tl, 0, impl="pallas", window=window)
    clean = attend()
    operand = pa._grouped_operand

    def planted(q, kvh, dtype):
        qk = operand(q, kvh, dtype)
        assert qk.shape[2] > g * t
        bad = jnp.asarray([jnp.nan, jnp.inf, -jnp.inf], dtype)
        fill = bad[jnp.arange(qk.shape[2] - g * t) % 3]
        return qk.at[:, :, g * t:].set(fill[None, None, :, None])

    monkeypatch.setattr(pa, "_grouped_operand", planted)
    got = attend()
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_array_equal(got, clean)
