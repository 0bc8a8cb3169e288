"""The served gated delta rule (ops/gdn.py): chunk rows and step rows from a
carried state against the token recurrence, the identity of pad positions,
dead rows, the tie to ops/kda.py, the kernels (interpreted) against their
jax.numpy spellings, the convolution's carried history."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import gdn, kda
from paddle_tpu.ops.kda_prep import causal_conv

SHAPES = [(6, 24, 48), (2, 96, 192)]          # heads, dk, dv


def _rows(seed, n, w, h, dk, dv, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l2 = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = l2(jax.random.normal(ks[0], (n, w, h, dk))).astype(dtype)
    k = l2(jax.random.normal(ks[1], (n, w, h, dk))).astype(dtype)
    v = jax.random.normal(ks[2], (n, w, h, dv)).astype(dtype)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (n, w, h)) - 1.0)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (n, w, h)))
    s0 = jax.random.normal(ks[5], (n, h, dk, dv))
    return q, k, v, g, beta, s0


def _stack(s0, layers=2, layer=1):
    """A state stack whose slots 1.. of ``layer`` hold ``s0``'s rows."""
    n = s0.shape[0]
    packed = gdn.pack_state(s0)
    state = jnp.full((layers, n + 1) + packed.shape[1:], 7.0, jnp.float32)
    return state.at[layer, 1:].set(packed)


@pytest.mark.parametrize("h,dk,dv", SHAPES)
def test_pack_state_round_trip(h, dk, dv):
    s = jax.random.normal(jax.random.PRNGKey(0), (3, h, dk, dv))
    p = gdn.pack_state(s)
    assert p.shape == (3, h // 2, dk, 2 * dv)
    np.testing.assert_array_equal(gdn.unpack_state(p, h), s)
    np.testing.assert_array_equal(p[:, 0, :, dv:], s[:, 1])


@pytest.mark.parametrize("h,dk,dv", SHAPES)
@pytest.mark.parametrize("w", [64, 80])
def test_chunk_rows_from_a_state_equal_the_recurrence(h, dk, dv, w):
    q, k, v, g, beta, s0 = _rows(1, 2, w, h, dk, dv)
    want_o, want_s = gdn.gdn_recurrent(q, k, v, g, beta, s0)
    o, s1 = gdn.xla_chunk(q, k, v, g, beta, s0, jnp.full((2,), w))
    np.testing.assert_allclose(o, want_o, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s1, want_s, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("h,dk,dv", SHAPES)
def test_step_rows_equal_the_recurrence(h, dk, dv):
    q, k, v, g, beta, s0 = _rows(2, 3, 5, h, dk, dv)
    want_o, want_s = gdn.gdn_recurrent(q, k, v, g, beta, s0)
    state, slots = _stack(s0), jnp.arange(1, 4)
    outs = []
    for t in range(5):
        o, state = gdn.gdn_step_rows(q[:, t], k[:, t], v[:, t], g[:, t],
                                     beta[:, t], state, 1, slots)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o, atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(gdn.unpack_state(state[1, 1:], h), want_s,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(state[0], 7.0)    # the other layer


@pytest.mark.parametrize("h,dk,dv", SHAPES)
def test_a_padded_last_chunk_is_the_identity_on_the_state(h, dk, dv):
    q, k, v, g, beta, s0 = _rows(3, 3, 64, h, dk, dv)
    row_len = jnp.asarray([64, 23, 0])
    o, s1 = gdn.xla_chunk(q, k, v, g, beta, s0, row_len)
    for r, n in enumerate([64, 23]):
        want_o, want_s = gdn.gdn_recurrent(
            q[r:r + 1, :n], k[r:r + 1, :n], v[r:r + 1, :n], g[r:r + 1, :n],
            beta[r:r + 1, :n], s0[r:r + 1])
        np.testing.assert_allclose(s1[r:r + 1], want_s, atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(o[r:r + 1, :n], want_o, atol=2e-4,
                                   rtol=2e-4)
    np.testing.assert_array_equal(s1[2], s0[2])     # no token: bit for bit


def test_below_the_floor_the_chunk_is_the_recurrence_at_the_floor():
    """``g < kda.G_MIN`` a token: the chunked form takes it at the floor (a
    decay of 1.2e-4 where the recurrence's is smaller); the step takes it
    as it is."""
    q, k, v, g, beta, s0 = _rows(11, 1, 64, 6, 24, 48)
    g = g.at[:, 20].set(-30.0)
    o, s1 = gdn.xla_chunk(q, k, v, g, beta, s0, jnp.full((1,), 64))
    want_o, want_s = gdn.gdn_recurrent(q, k, v, jnp.maximum(g, kda.G_MIN),
                                       beta, s0)
    np.testing.assert_allclose(o, want_o, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s1, want_s, atol=2e-4, rtol=2e-4)
    exact_o, _ = gdn.gdn_recurrent(q, k, v, g, beta, s0)
    assert np.abs(np.asarray(o - exact_o))[:, 20:].max() < 5e-3


@pytest.mark.parametrize("h,dk,dv", SHAPES)
def test_a_dead_rows_state_is_untouched_bit_for_bit(h, dk, dv):
    q, k, v, g, beta, s0 = _rows(4, 3, 1, h, dk, dv)
    state = _stack(s0)
    slots = jnp.asarray([1, 0, 3])                  # row 1 is dead
    _, after = gdn.gdn_step_rows(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], state, 1, slots)
    np.testing.assert_array_equal(after[1, 2], state[1, 2])
    assert not np.array_equal(after[1, 1], state[1, 1])
    _, after = gdn.gdn_chunk_rows(
        *_rows(5, 3, 64, h, dk, dv)[:5], state, 1, slots,
        jnp.zeros((3,), bool), jnp.asarray([64, 64, 9]))
    np.testing.assert_array_equal(after[1, 2], state[1, 2])


@pytest.mark.parametrize("h,dk,dv", SHAPES)
def test_the_scalar_gate_is_kdas_gate_broadcast(h, dk, dv):
    q, k, v, g, beta, s0 = _rows(6, 2, 128, h, dk, dv)
    want = kda.kda_recurrent(q, k, v, jnp.broadcast_to(
        g[..., None], q.shape), beta)
    o, _ = gdn.xla_chunk(q, k, v, g, beta, jnp.zeros_like(s0),
                         jnp.full((2,), 128))
    np.testing.assert_allclose(o, want, atol=2e-4, rtol=2e-4)
    # and through the trained scan's own entry
    np.testing.assert_allclose(
        kda.kda_attention(q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                          beta), want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("h,dk,dv", [(6, 24, 64), (2, 96, 192)])
def test_the_step_kernel_equals_its_xla_spelling(h, dk, dv):
    q, k, v, g, beta, s0 = _rows(7, 4, 1, h, dk, dv)
    state = _stack(s0)
    slots = jnp.asarray([2, 0, 4, 1])
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    want_o, want_s = gdn.xla_step(*args, gdn.unpack_state(
        state[1, slots], h))
    o, after = gdn.pallas_step(*args, state, 1, slots)
    live = np.asarray(slots) > 0
    np.testing.assert_allclose(o[live], want_o[live], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        gdn.unpack_state(after[1, slots], h)[live], want_s[live], atol=1e-5,
        rtol=1e-5)
    np.testing.assert_array_equal(after[1, 3], state[1, 3])   # no row's
    np.testing.assert_array_equal(after[0], state[0])


@pytest.mark.parametrize("h,dk,dv", [(6, 24, 64), (2, 96, 192)])
def test_the_chunk_kernel_equals_its_xla_spelling(h, dk, dv):
    q, k, v, g, beta, s0 = _rows(8, 3, 128, h, dk, dv)
    state = _stack(s0)
    slots = jnp.asarray([3, 1, 0])
    fresh = jnp.asarray([False, True, False])
    row_len = jnp.asarray([128, 70, 0])
    s_in = jnp.where(fresh[:, None, None, None], 0.0,
                     gdn.unpack_state(state[1, slots], h))
    want_o, want_s = gdn.xla_chunk(q, k, v, g, beta, s_in, row_len)
    o, after = gdn.pallas_chunk(q, k, v, g, beta, state, 1, slots, fresh,
                                row_len)
    for r, n in enumerate([128, 70]):
        np.testing.assert_allclose(o[r, :n], want_o[r, :n], atol=2e-4,
                                   rtol=2e-4)
    np.testing.assert_allclose(gdn.unpack_state(after[1, slots[:2]], h),
                               want_s[:2], atol=2e-4, rtol=2e-4)
    np.testing.assert_array_equal(after[1, 2], state[1, 2])   # no row's
    np.testing.assert_array_equal(after[0], state[0])


def test_the_convolution_carries_its_history_across_pieces():
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    x = jax.random.normal(ks[0], (2, 13, 16))
    taps = jax.random.normal(ks[1], (4, 16))
    want = causal_conv(x, taps)
    hist = jnp.zeros((3, 2, 16))
    got = []
    # pieces of 5 (rows of width 5 holding 5, 5, then 1 token), as a prompt's
    # chunks go, and then one token a step, as decode ticks go
    for lo, n in [(0, 5), (5, 5), (10, 1)]:
        piece = jnp.pad(x[:, lo:lo + n], ((0, 0), (0, 5 - n), (0, 0)))
        y, hist = gdn.conv_rows(piece, taps, hist, jnp.full((2,), n))
        got.append(y[:, :n])
    np.testing.assert_array_equal(hist, jnp.moveaxis(x[:, 8:11], 1, 0))
    for t in (11, 12):
        y, hist = gdn.conv_step(x[:, t], taps, hist)
        got.append(y[:, None])
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, atol=1e-5)
    np.testing.assert_array_equal(hist, jnp.moveaxis(x[:, 10:13], 1, 0))
    # a row of no tokens leaves the history as it was
    _, same = gdn.conv_rows(x[:, :5], taps, hist, jnp.zeros((2,), jnp.int32))
    np.testing.assert_array_equal(same, hist)


def test_the_path_is_observed_and_counted(monkeypatch):
    from paddle_tpu.profiler import metrics

    assert gdn.gdn_path(30, 96, 192) == "xla"       # the CPU
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    assert gdn.gdn_path(30, 96, 192) == "pallas"
    assert gdn.gdn_path(6, 24, 48) == "xla"         # pairs of 96 lanes
    assert gdn.gdn_path(3, 96, 192) == "xla"        # no pairs
    monkeypatch.delenv("PADDLE_TPU_TARGET_PLATFORM")
    before = metrics.registry().counter("gdn/step_calls{path=xla}").value
    q, k, v, g, beta, s0 = _rows(10, 2, 1, 2, 8, 16)
    gdn.gdn_step_rows(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                      _stack(s0), 0, jnp.asarray([1, 2]))
    assert metrics.registry().counter(
        "gdn/step_calls{path=xla}").value == before + 1
