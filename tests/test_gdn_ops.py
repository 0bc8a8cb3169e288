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


# (slots, fresh, row_len) a row: full rows, a partial last chunk and rows of
# no tokens side by side, entering at zero and carried; the last case is the
# chunk row of a tick without a chunk
CHUNK_ROWS = {
    "full-partial-null": ([3, 1, 0], [False, True, False], [128, 70, 0]),
    "empty-beside-full": ([2, 3, 1], [False, False, True], [0, 128, 0]),
    "empty-beside-partial": ([1, 2, 3], [True, False, False], [70, 0, 23]),
    "empty-first-and-last": ([3, 2, 1], [True, True, False], [0, 128, 0]),
    "all-empty": ([2, 0, 3], [False, False, True], [0, 0, 0]),
}


@pytest.mark.parametrize("rows", list(CHUNK_ROWS))
@pytest.mark.parametrize("h,dk,dv", [(6, 24, 64), (2, 96, 192)])
def test_the_chunk_kernel_equals_its_xla_spelling(h, dk, dv, rows):
    slots, fresh, row_len = (jnp.asarray(a) for a in CHUNK_ROWS[rows])
    q, k, v, g, beta, s0 = _rows(8, 3, 128, h, dk, dv)
    state = _stack(s0)
    s_in = jnp.where(fresh[:, None, None, None], 0.0,
                     gdn.unpack_state(state[1, slots], h))
    want_o, want_s = gdn.xla_chunk(q, k, v, g, beta, s_in, row_len)
    o, after = gdn.pallas_chunk(q, k, v, g, beta, state, 1, slots, fresh,
                                row_len)
    got_s = gdn.unpack_state(after[1, slots], h)
    for r, (slot, _, n) in enumerate(zip(*CHUNK_ROWS[rows])):
        if n:
            np.testing.assert_allclose(o[r, :n], want_o[r, :n], atol=2e-4,
                                       rtol=2e-4)
            np.testing.assert_allclose(got_s[r], want_s[r], atol=2e-4,
                                       rtol=2e-4)
        else:
            # a row of no tokens: zeros out (every block written), and its
            # slot as it was bit for bit, ``fresh`` or not
            np.testing.assert_array_equal(o[r], 0.0)
            np.testing.assert_array_equal(after[1, slot], state[1, slot])
    for free in set(range(state.shape[1])) - set(CHUNK_ROWS[rows][0]):
        np.testing.assert_array_equal(after[1, free], state[1, free])
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


# ---------------------------------------------------------------------------
# the pass between projections and rule (gdn_prep_step, gdn_prep_chunk)
# ---------------------------------------------------------------------------
PREP = (4, 96, 192)                            # heads, dk, dv: C = 1,536


def _prep_inputs(seed, shape, pool_dtype, dtype, slots=5, layers=2):
    h, dk, dv = PREP
    c = 2 * h * dk + h * dv
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], shape + (c,)).astype(dtype)
    taps = jax.random.uniform(ks[1], (4, c), minval=-0.5,
                              maxval=0.5).astype(dtype)
    conv = jax.random.normal(
        ks[2], (layers, 3, gdn.conv_slot_rows(slots), c)).astype(pool_dtype)
    # the null slot and the rows past the last slot hold zeros, as a pool's
    conv = conv.at[:, :, 0].set(0).at[:, :, slots + 1:].set(0)
    return x, taps, conv


PREP_CASES = {
    # decode rows: slots of the rows
    "decode live rows": ("step", [3, 1, 5, 2, 4]),
    "decode one dead row": ("step", [3, 0, 5, 2, 4, 1]),
    "decode dead rows collide": ("step", [0, 2, 0, 0, 5, 0, 0, 1]),
    "decode all dead": ("step", [0] * 8),
    # chunk rows: (slot, fresh, row_len) a row, rows of 16
    "chunk whole carried": ("chunk", [(2, False, 16)]),
    "chunk whole fresh": ("chunk", [(4, True, 16)]),
    "chunk partial carried": ("chunk", [(1, False, 7)]),
    "chunk partial fresh": ("chunk", [(5, True, 2)]),
    "chunk no token": ("chunk", [(3, False, 0)]),
    "chunk no token fresh": ("chunk", [(3, True, 0)]),
    "chunk dead row": ("chunk", [(0, False, 0)]),
    "chunk two rows": ("chunk", [(2, False, 9), (5, True, 16)]),
}


@pytest.mark.parametrize("cols", [384, 2048])
@pytest.mark.parametrize("pool_dtype,dtype", [
    (jnp.float32, jnp.float32), (jnp.bfloat16, jnp.bfloat16)])
@pytest.mark.parametrize("case", sorted(PREP_CASES))
def test_the_prep_pass_equals_its_xla_spelling(case, pool_dtype, dtype, cols,
                                               monkeypatch):
    """q, k, v within one rounding of the reference (the kernel rounds
    once, the spelling after the SiLU and after the norm), the history the
    rows leave in the pool bit for bit, every other row of the pool
    untouched."""
    monkeypatch.setattr(gdn, "_PREP_COLS", cols)    # 4 column tiles, or 2
    h, dk, dv = PREP
    kind, rows = PREP_CASES[case]
    if kind == "step":
        slots = jnp.asarray(rows, jnp.int32)
        x, taps, conv = _prep_inputs(21, (len(rows),), pool_dtype, dtype)
        fresh = row_len = None
    else:
        slots = jnp.asarray([r[0] for r in rows], jnp.int32)
        fresh = jnp.asarray([r[1] for r in rows])
        row_len = jnp.asarray([r[2] for r in rows], jnp.int32)
        x, taps, conv = _prep_inputs(22, (len(rows), 16), pool_dtype, dtype)
    want = gdn.xla_prep(x, taps, conv, 1, slots, fresh, row_len, h, dk)
    q, k, v, after = gdn.pallas_prep(x, taps, conv, 1, slots, fresh, row_len,
                                     h, dk)
    assert q.shape == x.shape[:-1] + (h, dk) and v.shape[-2:] == (h, dv)
    assert after.dtype == conv.dtype and q.dtype == x.dtype
    live = np.asarray(slots) > 0
    if kind == "chunk":
        live = live & (np.asarray(row_len) > 0)
    tol = 2e-5 if dtype == jnp.float32 else 2 ** -7
    for got, ref in zip((q, k, v), want[:3]):
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[live], np.asarray(ref, np.float32)[
                live], atol=tol, rtol=tol)
        assert np.isfinite(np.asarray(got, np.float32)).all()
    # every tenant's slot as the reference leaves it: the rows' own row for
    # row, the others untouched (the null slot's content is nobody's)
    after, conv = np.asarray(after, np.float32), np.asarray(conv, np.float32)
    np.testing.assert_array_equal(
        after[1][:, 1:], np.asarray(want[3], np.float32)[1][:, 1:])
    idle = [s for s in range(1, conv.shape[2])
            if s not in np.asarray(slots).tolist()]
    np.testing.assert_array_equal(after[1][:, idle], conv[1][:, idle])
    np.testing.assert_array_equal(after[0], conv[0])        # the other layer


def test_the_prep_path_is_observed_and_counted(monkeypatch):
    from paddle_tpu.profiler import metrics

    x, conv = (40, 11520), (12, 3, gdn.conv_slot_rows(40), 11520)
    assert gdn.conv_slot_rows(40) == 48 and gdn.conv_slot_rows(15) == 16
    assert gdn.prep_path(x, conv, 30, 96) == "xla"          # the CPU
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    assert gdn.prep_path(x, conv, 30, 96) == "pallas"
    assert gdn.prep_path((1, 256, 11520), conv, 30, 96) == "pallas"
    assert gdn._prep_cols(30, 96, 192) == (384, 1920)
    assert gdn.prep_path((3, 11520), conv, 30, 96) == "xla"     # rows
    assert gdn.prep_path(x, (12, 3, 41, 11520), 30, 96) == "xla"  # slots
    assert gdn.prep_path((40, 6 * 24 * 4), (2, 3, 48, 6 * 24 * 4), 6,
                         24) == "xla"       # heads that fill no lanes
    monkeypatch.delenv("PADDLE_TPU_TARGET_PLATFORM")
    count = lambda: metrics.registry().counter(             # noqa: E731
        "gdn/prep_calls{path=xla}").value
    before = count()
    x, taps, conv = _prep_inputs(23, (3,), jnp.float32, jnp.float32)
    q, k, v, after = gdn.gdn_prep_rows(x, taps, conv, 0, jnp.asarray(
        [2, 0, 1]), None, None, 4, 96)
    assert count() == before + 1
    want = gdn.xla_prep(x, taps, conv, 0, jnp.asarray([2, 0, 1]), None,
                        None, 4, 96)
    np.testing.assert_array_equal(q, want[0])
    np.testing.assert_array_equal(after, want[3])


# --- a decay a key channel (Kimi Delta Attention; ISSUE 49) -------------------
def _channel_rows(seed, n, w, h, dk, dv):
    """``_rows`` with ``g`` a key channel, bounded as Ling-3.0's gate bounds
    it (in (-5, 0)), and ``beta`` in (0, 1)."""
    q, k, v, _, beta, s0 = _rows(seed, n, w, h, dk, dv)
    g = -5.0 * jax.nn.sigmoid(
        jax.random.normal(jax.random.PRNGKey(seed + 100), (n, w, h, dk)))
    return q, k, v, g, beta / 2, s0


@pytest.mark.parametrize("h,dk,dv", [(4, 16, 16), (2, 128, 128)])
def test_the_per_channel_rule_from_a_state_equals_kdas_recurrence(h, dk, dv):
    """From a zero state the served rule is ``ops/kda.kda_recurrent`` itself;
    from a carried one the chunked form and the step are the recurrence."""
    q, k, v, g, beta, s0 = _channel_rows(21, 2, 80, h, dk, dv)
    zero = jnp.zeros_like(s0)
    o0, _ = gdn.gdn_recurrent(q, k, v, g, beta, zero)
    np.testing.assert_allclose(o0, kda.kda_recurrent(q, k, v, g, beta),
                               atol=1e-5, rtol=1e-5)
    want_o, want_s = gdn.gdn_recurrent(q, k, v, g, beta, s0)
    o, s1 = gdn.xla_chunk(q, k, v, g, beta, s0, jnp.full((2,), 80))
    np.testing.assert_allclose(o, want_o, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s1, want_s, atol=2e-4, rtol=2e-4)
    # a decay equal over a head's channels is the scalar rule
    flat = jnp.broadcast_to(g[..., :1], g.shape)
    a, sa = gdn.gdn_recurrent(q, k, v, flat, beta, s0)
    b, sb = gdn.gdn_recurrent(q, k, v, g[..., 0], beta, s0)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(sa, sb, atol=1e-5, rtol=1e-5)
    # and the channels matter: the mean over them is another model
    mean = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    assert np.abs(gdn.gdn_recurrent(q, k, v, mean, beta, s0)[0]
                  - want_o).max() > 1e-2


@pytest.mark.parametrize("h,dk,dv", [(4, 16, 16), (2, 128, 128)])
def test_per_channel_step_rows_equal_the_recurrence(h, dk, dv):
    q, k, v, g, beta, s0 = _channel_rows(22, 3, 5, h, dk, dv)
    want_o, want_s = gdn.gdn_recurrent(q, k, v, g, beta, s0)
    state, slots = _stack(s0), jnp.arange(1, 4)
    # heads of whole tiles lie alone, the others in pairs
    assert state.shape[2:] == ((h, dk, dv) if dv % 128 == 0
                               else (h // 2, dk, 2 * dv))
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
    # chunk rows through the entry: a fresh row enters at zero, positions
    # past a row's length change nothing
    q, k, v, g, beta, s0 = _channel_rows(23, 2, 64, h, dk, dv)
    state = _stack(s0)
    o, after = gdn.gdn_chunk_rows(q, k, v, g, beta, state, 1,
                                  jnp.asarray([2, 1]),
                                  jnp.asarray([False, True]),
                                  jnp.asarray([40, 64]))
    want_o, want_s = gdn.gdn_recurrent(         # row 0 from slot 2's state
        q[:1, :40], k[:1, :40], v[:1, :40], g[:1, :40], beta[:1, :40],
        s0[1:])
    np.testing.assert_allclose(o[0, :40], want_o[0], atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(gdn.unpack_state(after[1, 2:3], h), want_s,
                               atol=2e-4, rtol=2e-4)
    fresh_o, _ = gdn.gdn_recurrent(q[1:], k[1:], v[1:], g[1:], beta[1:],
                                   jnp.zeros_like(s0[:1]))
    np.testing.assert_allclose(o[1], fresh_o[0], atol=2e-4, rtol=2e-4)


def test_the_per_channel_step_kernel_equals_its_xla_spelling():
    h, dk, dv = 2, 128, 128
    q, k, v, g, beta, s0 = _channel_rows(24, 4, 1, h, dk, dv)
    state = _stack(s0)
    slots = jnp.asarray([2, 0, 4, 1])
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    want_o, want_s = gdn.xla_step(*args, state[1, slots])
    o, after = gdn.pallas_kda_step(*args, state, 1, slots)
    live = np.asarray(slots) > 0
    np.testing.assert_allclose(o[live], want_o[live], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(after[1, slots][live], want_s[live],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(after[1, 3], state[1, 3])   # no row's
    np.testing.assert_array_equal(after[0], state[0])


def test_the_per_channel_chunk_kernel_equals_its_xla_spelling():
    h, dk, dv = 2, 128, 128
    q, k, v, g, beta, s0 = _channel_rows(25, 3, 128, h, dk, dv)
    state = _stack(s0)
    slots = jnp.asarray([3, 1, 0])
    fresh = jnp.asarray([False, True, False])
    row_len = jnp.asarray([128, 70, 0])
    s_in = jnp.where(fresh[:, None, None, None], 0.0, state[1, slots])
    want_o, want_s = gdn.xla_chunk(q, k, v, g, beta, s_in, row_len)
    o, after = gdn.pallas_kda_chunk(q, k, v, g, beta, state, 1, slots,
                                    fresh, row_len)
    for r, n in enumerate([128, 70]):
        np.testing.assert_allclose(o[r, :n], want_o[r, :n], atol=2e-4,
                                   rtol=2e-4)
    np.testing.assert_allclose(after[1, slots[:2]], want_s[:2], atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_array_equal(after[1, 2], state[1, 2])   # no row's
    np.testing.assert_array_equal(after[0], state[0])


def test_the_per_channel_path_is_observed_and_the_scalar_one_is_as_it_was(
        monkeypatch):
    from paddle_tpu.profiler import metrics

    assert gdn.kda_path(32, 128, 128) == "xla"      # the CPU
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    assert gdn.kda_path(32, 128, 128) == "pallas"
    assert gdn.kda_path(4, 16, 16) == "xla"         # keys of no whole tile
    # the scalar gate's kernels take heads in pairs: Olmo-Hybrid's as ever,
    # and never heads that lie alone
    assert gdn.gdn_path(30, 96, 192) == "pallas"
    assert gdn.gdn_path(32, 128, 128) == "xla"
    monkeypatch.delenv("PADDLE_TPU_TARGET_PLATFORM")
    assert gdn.pack_state(jnp.zeros((30, 96, 192))).shape == (15, 96, 384)
    assert gdn.pack_state(jnp.zeros((32, 128, 128))).shape == (32, 128, 128)
    before = metrics.registry().counter("gdn/step_calls{path=xla}").value
    q, k, v, g, beta, s0 = _channel_rows(26, 2, 1, 2, 8, 16)
    gdn.gdn_step_rows(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                      _stack(s0), 1, jnp.arange(1, 3))
    assert metrics.registry().counter(
        "gdn/step_calls{path=xla}").value == before + 1
