"""``ops/ssd.py``: the served Mamba-2 rule. The decode step, the chunked scan
and the pass between projection and rule, each in its ``jax.numpy`` spelling
and as the Pallas kernel interpreted, against the token-by-token recurrence
(``ssd_recurrent``, and a plain numpy loop that shares nothing with it)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import gdn, ssd

H, G, P, N = 4, 2, 8, 16


def _draw(seed, n, w=None, dtype=jnp.float32, h=H, g=G, p=P, ns=N):
    """Rows of ``n`` (``[n, ...]``) or ``[n, w, ...]``: x, B, C, dt, A, D."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    lead = (n,) if w is None else (n, w)
    x = jax.random.normal(ks[0], lead + (h, p), jnp.float32).astype(dtype)
    B = jax.random.normal(ks[1], lead + (g, ns), jnp.float32).astype(dtype)
    C = jax.random.normal(ks[2], lead + (g, ns), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[3], lead + (h,)) - 1.0)
    A = -jax.random.uniform(ks[4], (h,), jnp.float32, 1.0, 16.0)
    D = jax.random.normal(ks[5], (h,), jnp.float32)
    return x, B, C, dt, A, D


def _numpy_recurrence(x, B, C, dt, A, D, s0):
    """The rule as the issue writes it, a token and a head at a time, the
    state as ``[P, N]``: shares no code with ``ops/ssd.py``."""
    x, B, C, dt, A, D = (np.asarray(a, np.float64) for a in (x, B, C, dt, A,
                                                             D))
    n, w, h, p = x.shape
    per = h // B.shape[2]
    s = np.swapaxes(np.asarray(s0, np.float64), -1, -2).copy()  # [n,H,P,N]
    y = np.zeros((n, w, h, p))
    for r in range(n):
        for t in range(w):
            for i in range(h):
                g = i // per
                s[r, i] = np.exp(dt[r, t, i] * A[i]) * s[r, i] \
                    + dt[r, t, i] * np.outer(x[r, t, i], B[r, t, g])
                y[r, t, i] = s[r, i] @ C[r, t, g] + D[i] * x[r, t, i]
    return y, np.swapaxes(s, -1, -2)


def _state(seed, slots, layers=2, h=H, ns=N, p=P):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (layers, slots + 1, h, ns, p), jnp.float32)


def test_the_recurrence_is_the_rule_as_written():
    x, B, C, dt, A, D = _draw(0, 2, 5)
    s0 = _state(1, 2)[0, 1:]
    y, s1 = ssd.ssd_recurrent(x, B, C, dt, A, D, s0)
    want_y, want_s = _numpy_recurrence(x, B, C, dt, A, D, s0)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1, want_s, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_step_float32(impl):
    x, B, C, dt, A, D = _draw(2, 3)
    state = _state(3, 4)
    slots = jnp.asarray([2, 0, 4], jnp.int32)
    want_y, want_s = ssd.xla_step(x, B, C, dt, A, D, state[1, slots])
    if impl == "xla":
        y, new = ssd.ssd_step_rows(x, B, C, dt, A, D, state, 1, slots)
    else:
        y, new = ssd.pallas_step(x, B, C, dt, A, D, state, 1, slots)
    ref_y, ref_s = _numpy_recurrence(*(a[:, None] for a in (x, B, C, dt)), A,
                                     D, state[1, slots])
    np.testing.assert_allclose(y, ref_y[:, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new[1, slots[0]], want_s[0], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(new[1, slots[2]], ref_s[2], rtol=1e-5,
                               atol=1e-5)
    # the other layer and the slots no row carries are as they were
    np.testing.assert_array_equal(new[0], state[0])
    np.testing.assert_array_equal(new[1, jnp.asarray([1, 3])],
                                  state[1, jnp.asarray([1, 3])])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_step_with_bf16_operands(impl):
    x, B, C, dt, A, D = _draw(4, 2, dtype=jnp.bfloat16)
    state = _state(5, 2)
    slots = jnp.asarray([1, 2], jnp.int32)
    step = ssd.ssd_step_rows if impl == "xla" else ssd.pallas_step
    y, new = step(x, B, C, dt, A, D, state, 0, slots)
    ref_y, ref_s = _numpy_recurrence(
        *(a[:, None].astype(jnp.float32) for a in (x, B, C)), dt[:, None], A,
        D, state[0, slots])
    # the operands are exact in float32 and the step is float32: no loss
    np.testing.assert_allclose(y, ref_y[:, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new[0, 1:], ref_s, rtol=1e-5, atol=1e-5)
    assert new.dtype == jnp.float32 and y.dtype == jnp.float32


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("w", [ssd.CHUNK, 2 * ssd.CHUNK])
def test_chunk_float32_against_the_recurrence(impl, w):
    x, B, C, dt, A, D = _draw(6, 2, w)
    state = _state(7, 3)
    slots = jnp.asarray([3, 1], jnp.int32)
    fresh = jnp.asarray([False, False])
    row_len = jnp.asarray([w, w], jnp.int32)
    chunk = ssd.ssd_chunk_rows if impl == "xla" else ssd.pallas_chunk
    y, new = chunk(x, B, C, dt, A, D, state, 1, slots, fresh, row_len)
    want_y, want_s = ssd.ssd_recurrent(x, B, C, dt, A, D, state[1, slots])
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(new[1, slots], want_s, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(new[0], state[0])
    np.testing.assert_array_equal(new[1, 2], state[1, 2])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunk_with_bf16_operands(impl):
    w = ssd.CHUNK
    x, B, C, dt, A, D = _draw(8, 1, w, dtype=jnp.bfloat16)
    state = _state(9, 1)
    slots = jnp.asarray([1], jnp.int32)
    chunk = ssd.ssd_chunk_rows if impl == "xla" else ssd.pallas_chunk
    y, new = chunk(x, B, C, dt, A, D, state, 0, slots, jnp.asarray([False]),
                   jnp.asarray([w], jnp.int32))
    f = lambda a: a.astype(jnp.float32)                     # noqa: E731
    want_y, want_s = ssd.ssd_recurrent(f(x), f(B), f(C), dt, A, D,
                                       state[0, slots])
    # the products' operands are rounded to bf16 (2^-9 a term), the sums and
    # the carried state are float32
    scale = float(jnp.max(jnp.abs(want_y)))
    np.testing.assert_allclose(y, want_y, atol=2e-2 * scale)
    np.testing.assert_allclose(new[0, slots], want_s,
                               atol=2e-2 * float(jnp.max(jnp.abs(want_s))))
    assert new.dtype == jnp.float32


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_ragged_row_and_a_fresh_one(impl):
    w = ssd.CHUNK
    x, B, C, dt, A, D = _draw(10, 3, w)
    state = _state(11, 3)
    slots = jnp.asarray([2, 3, 0], jnp.int32)
    fresh = jnp.asarray([False, True, False])
    row_len = jnp.asarray([37, w, 0], jnp.int32)
    chunk = ssd.ssd_chunk_rows if impl == "xla" else ssd.pallas_chunk
    y, new = chunk(x, B, C, dt, A, D, state, 0, slots, fresh, row_len)
    # a row of 37 tokens: the recurrence over its 37
    cut = lambda a, r, n: a[r:r + 1, :n]                    # noqa: E731
    y0, s0 = ssd.ssd_recurrent(*(cut(a, 0, 37) for a in (x, B, C, dt)), A, D,
                               state[0, 2:3])
    np.testing.assert_allclose(y[0, :37], y0[0], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(new[0, 2], s0[0], rtol=2e-4, atol=2e-4)
    # a fresh row enters at zero, whatever its slot held
    y1, s1 = ssd.ssd_recurrent(*(cut(a, 1, w) for a in (x, B, C, dt)), A, D,
                               jnp.zeros_like(state[0, 3:4]))
    np.testing.assert_allclose(y[1], y1[0], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(new[0, 3], s1[0], rtol=2e-4, atol=2e-4)
    # a row of no tokens leaves its slot (the null slot) as it was
    np.testing.assert_allclose(new[0, 0], state[0, 0], rtol=1e-6)
    np.testing.assert_array_equal(new[0, 1], state[0, 1])


def test_a_chunk_then_steps_is_the_recurrence_over_the_whole():
    w, more = ssd.CHUNK, 5
    x, B, C, dt, A, D = _draw(12, 1, w + more)
    state = _state(13, 1, layers=1)
    slots = jnp.asarray([1], jnp.int32)
    head = lambda a: a[:, :w]                               # noqa: E731
    y, st = ssd.ssd_chunk_rows(head(x), head(B), head(C), head(dt), A, D,
                               state, 0, slots, jnp.asarray([True]),
                               jnp.asarray([w], jnp.int32))
    ys = [y]
    for t in range(w, w + more):
        yt, st = ssd.ssd_step_rows(x[:, t], B[:, t], C[:, t], dt[:, t], A, D,
                                   st, 0, slots)
        ys.append(yt[:, None])
    want_y, want_s = ssd.ssd_recurrent(x, B, C, dt, A, D,
                                       jnp.zeros_like(state[0, 1:2]))
    np.testing.assert_allclose(jnp.concatenate(ys, 1), want_y, rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(st[0, 1:2], want_s, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_null_slot_is_never_read_into_a_live_row(impl):
    x, B, C, dt, A, D = _draw(14, 2)
    state = _state(15, 2).at[:, 0].set(jnp.nan)
    slots = jnp.asarray([1, 2], jnp.int32)
    step = ssd.ssd_step_rows if impl == "xla" else ssd.pallas_step
    y, new = step(x, B, C, dt, A, D, state, 0, slots)
    assert bool(jnp.all(jnp.isfinite(y)))
    assert bool(jnp.all(jnp.isfinite(new[:, 1:])))


# -- the pass between projection and rule -----------------------------------
def _prep_inputs(seed, n, slots_n, c, w=None, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    lead = (n,) if w is None else (n, w)
    x = jax.random.normal(ks[0], lead + (c,), jnp.float32).astype(dtype)
    taps = jax.random.uniform(ks[1], (4, c), jnp.float32, -0.5, 0.5)
    bias = jax.random.normal(ks[2], (c,), jnp.float32)
    conv = jax.random.normal(
        ks[3], (2, 3, gdn.conv_slot_rows(slots_n), c),
        jnp.float32).astype(dtype)
    return x, taps, bias, conv


def _conv_by_hand(x, taps, bias, hist):
    """``x`` [t, C] after ``hist`` [3, C], a position at a time."""
    cat = np.concatenate([np.asarray(hist, np.float64),
                          np.asarray(x, np.float64)])
    taps, bias = np.asarray(taps, np.float64), np.asarray(bias, np.float64)
    y = np.stack([sum(taps[j] * cat[t + j] for j in range(4)) + bias
                  for t in range(x.shape[0])])
    return y / (1.0 + np.exp(-y)), cat[-3:]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prep_of_decode_rows(impl):
    c = 256
    x, taps, bias, conv = _prep_inputs(20, 8, 9, c)
    slots = jnp.asarray([3, 0, 5, 9, 0, 1, 2, 4], jnp.int32)
    prep = ssd.xla_prep if impl == "xla" else ssd.pallas_prep
    y, new = prep(x, taps, bias, conv, 1, slots, None, None)
    for r, s in enumerate(np.asarray(slots)):
        if s == 0:
            continue
        want, left = _conv_by_hand(x[r:r + 1], taps, bias, conv[1, :, s])
        np.testing.assert_allclose(y[r], want[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(new[1, :, s], left, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(new[0], conv[0])
    free = jnp.asarray([6, 7, 8])
    np.testing.assert_array_equal(new[1][:, free], conv[1][:, free])
    if impl == "pallas":
        np.testing.assert_array_equal(new[1, :, 0], conv[1, :, 0])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prep_of_a_chunk_row(impl):
    c, w = 256, 16
    x, taps, bias, conv = _prep_inputs(21, 2, 4, c, w)
    slots = jnp.asarray([2, 4], jnp.int32)
    fresh = jnp.asarray([False, True])
    row_len = jnp.asarray([11, w], jnp.int32)
    prep = ssd.xla_prep if impl == "xla" else ssd.pallas_prep
    y, new = prep(x, taps, bias, conv, 0, slots, fresh, row_len)
    want, left = _conv_by_hand(x[0, :11], taps, bias, conv[0, :, 2])
    np.testing.assert_allclose(y[0, :11], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new[0, :, 2], left, rtol=1e-6, atol=1e-6)
    want, left = _conv_by_hand(x[1], taps, bias, jnp.zeros((3, c)))
    np.testing.assert_allclose(y[1], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new[0, :, 4], left, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(new[1], conv[1])
    np.testing.assert_array_equal(new[0, :, 3], conv[0, :, 3])


def test_prep_in_bf16_is_the_spelling():
    c = 256
    x, taps, bias, conv = _prep_inputs(22, 8, 9, c, dtype=jnp.bfloat16)
    slots = jnp.asarray([3, 0, 5, 9, 0, 1, 2, 4], jnp.int32)
    y0, c0 = ssd.xla_prep(x, taps, bias, conv, 0, slots, None, None)
    y1, c1 = ssd.pallas_prep(x, taps, bias, conv, 0, slots, None, None)
    live = np.asarray(slots) > 0
    np.testing.assert_allclose(np.asarray(y0, np.float32)[live],
                               np.asarray(y1, np.float32)[live], rtol=1e-2,
                               atol=1e-2)
    np.testing.assert_array_equal(np.asarray(c0[0, :, 1:10], np.float32),
                                  np.asarray(c1[0, :, 1:10], np.float32))


def test_the_paths_are_counted_where_traced():
    from paddle_tpu.profiler import metrics

    x, B, C, dt, A, D = _draw(30, 1)
    state = _state(31, 1)
    before = metrics.registry().counter("ssd/step_calls{path=xla}").value
    ssd.ssd_step_rows(x, B, C, dt, A, D, state, 0, jnp.asarray([1]))
    assert metrics.registry().counter(
        "ssd/step_calls{path=xla}").value == before + 1
    assert ssd.ssd_path(32, 256, 128) == "xla"       # the CPU is the target
    assert ssd.prep_path((80, 5120), (9, 3, 96, 5120)) == "xla"
    assert ssd._prep_cols(5120) == (512, 2560)
    assert ssd._prep_cols(96) is None
