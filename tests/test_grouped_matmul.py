"""``ops.grouped_matmul``: the Pallas kernels (interpreted on the CPU, at
sizes whose tiles the production rule picks: 128 rows, three steps of the
contraction one way and of the columns the other) against
``jax.lax.ragged_dot``, and which of the two paths a trace takes."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from paddle_tpu.distributed import context as dctx
from paddle_tpu.distributed.moe import dropless_moe
from paddle_tpu.ops import grouped_matmul as gm
from paddle_tpu.profiler import metrics

M, K, N, E = 640, 384, 256, 8           # a share is 80 rows
LAYOUTS = {
    "balanced": [80] * 8,
    "one_empty": [80, 80, 0, 160, 80, 80, 80, 80],
    "first_and_last_empty": [0, 100, 90, 110, 100, 120, 120, 0],
    "one_holds_7.9_shares": [2, 1, 632, 1, 1, 1, 1, 1],
}


def _nerr(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_the_production_tiles_of_the_test_sizes_are_small():
    assert gm.tile_for(M, K, N) == (128, 128, 256)
    assert gm.tile_for(M, N, K) == (128, 256, 128)


@pytest.mark.parametrize("transpose_rhs", [False, True],
                         ids=["rhs_ekn", "rhs_enk"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernels_match_ragged_dot_forward_and_gradients(layout,
                                                        transpose_rhs):
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    assert int(sizes.sum()) == M
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    lhs = jax.random.normal(ks[0], (M, K), jnp.float32)
    rhs = jax.random.normal(ks[1], (E, K, N), jnp.float32)
    w = jax.random.normal(ks[2], (M, N), jnp.float32)

    def kernels(a, b):
        if transpose_rhs:
            b = b.swapaxes(1, 2)
        out = gm.pallas_grouped_matmul(a, b, sizes, transpose_rhs)
        return jnp.sum(out * w), out

    def reference(a, b):
        out = jax.lax.ragged_dot(a, b, sizes)
        return jnp.sum(out * w), out

    def run(f):
        (value, out), grads = jax.jit(
            jax.value_and_grad(f, (0, 1), has_aux=True))(lhs, rhs)
        return value, out, grads

    value, out, grads = run(kernels)
    want_value, want_out, want_grads = run(reference)
    assert _nerr(out, want_out) < 1e-5
    assert abs(float(value) - float(want_value)) < 1e-4 * abs(
        float(want_value))
    assert _nerr(grads[0], want_grads[0]) < 1e-5        # towards the rows
    assert _nerr(grads[1], want_grads[1]) < 1e-5        # towards the weights
    # an empty group's weights get a gradient of exactly zero
    for e, size in enumerate(LAYOUTS[layout]):
        if size == 0:
            assert not np.asarray(grads[1][e]).any()


def test_kernels_in_bf16_round_as_ragged_dot_does():
    sizes = jnp.asarray(LAYOUTS["one_empty"], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    lhs = jax.random.normal(ks[0], (M, K), jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (E, K, N), jnp.bfloat16)
    out = gm.pallas_grouped_matmul(lhs, rhs, sizes)
    assert out.dtype == jnp.bfloat16
    assert _nerr(out, jax.lax.ragged_dot(lhs, rhs, sizes)) < 1e-2


def _two_device_mesh():
    return Mesh(np.array(jax.devices()[:2]), ("ep",))


@pytest.mark.parametrize("target,mesh,want", [
    ("cpu", None, "xla"), ("tpu", None, "pallas"),
    ("tpu", _two_device_mesh, "xla"), ("cpu", _two_device_mesh, "xla")])
def test_the_path_follows_platform_and_open_mesh(monkeypatch, target, mesh,
                                                 want):
    """What ``dropless_moe`` traces (``eval_shape``: nothing is compiled)
    and what it counted in the profiler's registry."""
    import contextlib

    from paddle_tpu import profiler

    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", target)
    metrics.registry().reset()
    t, h, f, e, k = 256, 128, 128, 8, 2
    shapes = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in
              ((t, h), (h, e), (e, h, f), (e, h, f), (e, f, h))]
    scope = dctx.kernel_scope(mesh()) if mesh else contextlib.nullcontext()
    with scope:
        assert gm.kernel_path(t * k, h, f) == want
        jaxpr = jax.make_jaxpr(lambda *a: dropless_moe(*a, top_k=k))(*shapes)
    assert ("pallas_call" in str(jaxpr)) == (want == "pallas")
    assert ("ragged_dot" in str(jaxpr)) == (want == "xla")
    counted = profiler.summary()["metrics"]
    assert counted["moe/grouped_matmul_calls{path=%s}" % want]["value"] == 3
    other = "xla" if want == "pallas" else "pallas"
    assert "moe/grouped_matmul_calls{path=%s}" % other not in counted


@pytest.mark.parametrize("shape", [(100, 128, 128), (256, 96, 128),
                                   (256, 128, 200)])
def test_a_shape_that_does_not_tile_falls_back(monkeypatch, shape):
    m, k, n = shape
    assert gm.tile_for(m, k, n) is None
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    assert gm.kernel_path(m, k, n) == "xla"
    monkeypatch.delenv("PADDLE_TPU_TARGET_PLATFORM")
    sizes = jnp.asarray([m - 60, 0, 60], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    lhs = jax.random.normal(ks[0], (m, k), jnp.float32)
    rhs = jax.random.normal(ks[1], (3, k, n), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(gm.grouped_matmul(lhs, rhs, sizes)),
        np.asarray(jax.lax.ragged_dot(lhs, rhs, sizes)))
    with pytest.raises(ValueError, match="does not tile"):
        gm.pallas_grouped_matmul(lhs, rhs, sizes)
