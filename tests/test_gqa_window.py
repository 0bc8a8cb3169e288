"""Grouped-query attention over K/V pages **under a sliding window**
(``ops/paged_attention.grouped_paged_attention(..., window=)``, ISSUE 57):
both spellings (the kernel interpreted) against a dense masked attention at
six and nine query heads a key/value head, decode rows and chunk rows, the
first page partly behind the window, pages behind it gone (null entries, NaN
on the pages themselves)."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import paged_attention as pa
from test_gqa_pages import PS, _filled

WINDOW = 21         # not whole pages: the first visible page is partly behind


def _dense_window(q, k, v, pos0, true_len, window):
    """q [T, NH, D] at positions ``pos0 + i`` over k, v [S, KVH, D]: query
    ``i`` sees ``pos0 + i - window < j <= pos0 + i``; float64."""
    t, nh, d = q.shape
    per = nh // k.shape[1]
    out = np.zeros((t, nh, d))
    for i in range(min(t, true_len)):
        at = pos0 + i
        seen = slice(max(at - window + 1, 0), at + 1)
        for j in range(nh):
            s = k[seen, j // per] @ q[i, j] / np.sqrt(d)
            w = np.exp(s - s.max())
            out[i, j] = (w / w.sum()) @ v[seen, j // per]
    return out


def _behind_gone(pool, table, pos0, window):
    """The table with every page wholly behind the rows' windows null, and
    NaN on the pages it named (``WindowSpace.free_behind``'s doing)."""
    table = np.array(table)
    pool = np.array(pool, np.float32)
    for r, p0 in enumerate(np.asarray(pos0)):
        oldest = max(int(p0) - window + 1, 0) // PS
        pool[:, table[r, :oldest]] = np.nan
        table[r, :oldest] = 0
    pool[:, 0] = 0.0
    return jnp.asarray(pool), jnp.asarray(table)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("group", [6, 9])
def test_decode_rows_see_their_window(impl, group):
    kvh, d = 2, 16
    lens = [37, 0, 63]
    pool, table, ks, vs = _filled(3, 2, 3, 8, kvh, d, lens)
    pool = jnp.nan_to_num(pool)
    pos0 = jnp.asarray([n - 1 if n else 0 for n in lens], jnp.int32)
    tl = jnp.asarray([1 if n else 0 for n in lens], jnp.int32)
    pool, table = _behind_gone(pool, table, pos0, WINDOW)
    q = np.random.default_rng(4).standard_normal(
        (3, 1, group * kvh, d)).astype(np.float32)
    got = pa.grouped_paged_attention(jnp.asarray(q), pool, table, pos0, tl,
                                     1, impl=impl, window=WINDOW)
    assert got.shape == q.shape and bool(jnp.all(jnp.isfinite(got)))
    for r, n in enumerate(lens):
        if n:
            want = _dense_window(q[r], ks[r][1], vs[r][1], n - 1, 1, WINDOW)
            np.testing.assert_allclose(got[r], want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("group", [6, 9])
def test_chunk_rows_see_their_window(impl, group):
    """Chunk rows of 8 queries, one of them a partial chunk (5 live) and
    one a pad row; the first chunk of a prompt, one whose window starts
    inside a page and one longer than the window behind it."""
    kvh, d, t = 2, 16, 8
    pos0s, tls = [0, 19, 44, 0], [8, 8, 5, 0]
    lens = [p + n for p, n in zip(pos0s, tls)]
    pool, table, ks, vs = _filled(5, 2, 4, 8, kvh, d, lens)
    pool = jnp.nan_to_num(pool)
    pos0, tl = jnp.asarray(pos0s, jnp.int32), jnp.asarray(tls, jnp.int32)
    pool, table = _behind_gone(pool, table, pos0, WINDOW)
    q = np.random.default_rng(6).standard_normal(
        (4, t, group * kvh, d)).astype(np.float32)
    got = pa.grouped_paged_attention(jnp.asarray(q), pool, table, pos0, tl,
                                     0, impl=impl, window=WINDOW)
    assert bool(jnp.all(jnp.isfinite(got)))
    for r, n in enumerate(tls):
        if n:
            want = _dense_window(q[r], ks[r][0], vs[r][0], pos0s[r], n,
                                 WINDOW)
            np.testing.assert_allclose(got[r, :n], want[:n], atol=2e-5,
                                       rtol=2e-5)


def test_a_window_longer_than_the_context_is_full_attention():
    kvh, d = 2, 16
    lens = [37, 9]
    pool, table, _, _ = _filled(7, 1, 2, 8, kvh, d, lens)
    pool = jnp.nan_to_num(pool)
    pos0 = jnp.asarray([n - 1 for n in lens], jnp.int32)
    tl = jnp.ones((2,), jnp.int32)
    q = jnp.asarray(np.random.default_rng(8).standard_normal(
        (2, 1, 12, d)), jnp.float32)
    full = pa.grouped_paged_attention(q, pool, table, pos0, tl, 0,
                                      impl="xla")
    for impl in ("xla", "pallas"):
        np.testing.assert_allclose(
            pa.grouped_paged_attention(q, pool, table, pos0, tl, 0, impl=impl,
                                       window=64), full, atol=2e-5)
