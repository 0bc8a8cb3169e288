"""``ops.grouped_matmul``: the Pallas kernels (interpreted on the CPU)
against ``jax.lax.ragged_dot``, which of the two paths a trace takes, and
the rule that cuts a group's matrix into grid steps (``tile_for``). The
first cases run under a budget of 32 K weights a step (``small_tiles``), at
which their sizes walk three steps of the contraction one way and of the
columns the other: the accumulator, which the production budget reaches
only past a contraction of 16,384; the cases after them run the rule as
it is, the whole contraction a step."""
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
# sides that are no powers of two, the whole contraction a step: 256 rows,
# eight groups of which three are empty and one holds one row
M2, K2, N2 = 256, 384, 640
WHOLE_LAYOUTS = {
    "whole_k_some_empty_one_row": [0, 70, 1, 0, 57, 100, 0, 28],
}
LAYOUTS_OF = {**{name: (M, K, N, sizes) for name, sizes in LAYOUTS.items()},
              **{name: (M2, K2, N2, sizes)
                 for name, sizes in WHOLE_LAYOUTS.items()}}


@pytest.fixture
def small_tiles(monkeypatch):
    """32 K weights a grid step: (M, K, N) cuts its contraction in three."""
    monkeypatch.setattr(gm, "_TILE_WEIGHTS", 2 ** 15)


def _nerr(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_the_tiles_of_the_test_sizes(small_tiles):
    assert gm.tile_for(M, K, N) == (128, 128, 256)
    assert gm.tile_for(M, N, K) == (128, 256, 128)


def test_the_tiles_of_the_sizes_that_are_no_powers_of_two():
    """Forward, the gradient towards the rows, the one towards the
    weights: each the whole contraction (for ``moe_tgmm``: the whole
    first side of a group's block) in one step."""
    assert gm.tile_for(M2, K2, N2) == (128, 384, 640)
    assert gm.tile_for(M2, N2, K2) == (128, 640, 384)


@pytest.mark.parametrize("transpose_rhs", [False, True],
                         ids=["rhs_ekn", "rhs_enk"])
@pytest.mark.parametrize("layout", list(LAYOUTS_OF))
def test_kernels_match_ragged_dot_forward_and_gradients(layout,
                                                        transpose_rhs,
                                                        request):
    m, k, n, groups = LAYOUTS_OF[layout]
    if layout in LAYOUTS:
        request.getfixturevalue("small_tiles")
    sizes = jnp.asarray(groups, jnp.int32)
    assert int(sizes.sum()) == m
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    lhs = jax.random.normal(ks[0], (m, k), jnp.float32)
    rhs = jax.random.normal(ks[1], (E, k, n), jnp.float32)
    w = jax.random.normal(ks[2], (m, n), jnp.float32)

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
    for e, size in enumerate(groups):
        if size == 0:
            assert not np.asarray(grads[1][e]).any()


@pytest.mark.parametrize("budget", ["small_tiles", None],
                         ids=["k_in_three_steps", "whole_k"])
def test_kernels_in_bf16_round_as_ragged_dot_does(budget, request):
    if budget:
        request.getfixturevalue(budget)
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
    # the tile a product walks its groups' matrices in, where it is the
    # kernel's: gate, up and down of [128, 128] are one tile, thrice
    tiles = {name: m["value"] for name, m in counted.items()
             if name.startswith("moe/grouped_matmul_tiles")}
    assert tiles == ({"moe/grouped_matmul_tiles{tile=128x128x128}": 3}
                     if want == "pallas" else {})


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


# ---------------------------------------------------------------------------
# the rule: tile_for
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cell,m,h,f,gate_up,down", [
    # the expert's [H, F]: gate and up contract H, down contracts F; the
    # backward forms take the same two tiles (d_rows of gate/up is down's
    # shape and the weights' gradient is its block's own shape)
    ("train-olmoe-1chip-4k", 32768, 2048, 1024,
     (128, 2048, 1024), (128, 1024, 2048)),
    ("serve-dots3-longdoc-backlog", 512, 5120, 1536,
     (128, 5120, 384), (128, 1536, 1280)),
    ("serve-dsv2-docqa-backlog", 640, 5120, 1536,
     (128, 5120, 384), (128, 1536, 1280)),
    ("train-solar-open2-1chip", 2560, 4096, 1280,
     (128, 4096, 256), (128, 1280, 1024)),
    ("serve-ling3-longgen-backlog", 1024, 2560, 768,
     (128, 2560, 768), (128, 768, 2560)),
])
def test_the_cells_tiles(cell, m, h, f, gate_up, down):
    """What each cell's grouped products compile to. OLMoE's are the whole
    matrix, as before PR 50's rule (its program is unchanged); Ling's
    expert is one step a visit."""
    assert gm.tile_for(m, h, f) == gate_up
    assert gm.tile_for(m, f, h) == down
    if cell in ("train-olmoe-1chip-4k", "serve-ling3-longgen-backlog"):
        assert gate_up[1:] == (h, f) and down[1:] == (f, h)


def test_olmoe_tiles_are_what_the_powers_of_two_gave():
    """The rule before PR 50: ``tn`` then ``tk`` from five powers of two
    under 2 M weights. OLMoE's six products (forward, d_rows, d_weights of
    [2048, 1024] and [1024, 2048]) are two shapes, and both tile as then."""
    def before(m, k, n):
        sizes = (2048, 1024, 512, 256, 128)
        tn = next(s for s in sizes if n % s == 0)
        tk = next(s for s in sizes if k % s == 0 and s * tn <= 2 ** 21)
        return 128, tk, tn

    for k, n in ((2048, 1024), (1024, 2048)):
        assert gm.tile_for(32768, k, n) == before(32768, k, n) == (128, k, n)


@pytest.mark.parametrize("k", range(128, 8192 + 1, 128))
def test_a_tile_divides_fits_and_holds_the_contraction_if_it_can(k):
    budget = gm._TILE_WEIGHTS
    for n in range(128, 8192 + 1, 128):
        tm, tk, tn = gm.tile_for(256, k, n)
        assert tm == 128 and k % tk == 0 and n % tn == 0
        assert tk % 128 == 0 and tn % 128 == 0
        assert tk * tn <= budget
        # 128 columns of the whole contraction fit: it is not cut, and no
        # wider divisor of the columns would have fitted
        assert k * 128 <= budget and tk == k
        assert all(n % d or k * d > budget
                   for d in range(tn + 128, n + 1, 128))


@pytest.mark.parametrize("k,n,tile", [
    (16384, 256, (128, 16384, 128)),        # just fits: 128 columns
    (16512, 256, (128, 5504, 256)),         # 129 x 128: cut at 43 x 128
    (32768, 128, (128, 16384, 128)),
    (16384 + 128 * 2, 1024, (128, 8320, 128)),
])
def test_a_contraction_is_cut_only_where_128_columns_do_not_fit(k, n, tile):
    assert gm.tile_for(128, k, n) == tile
    tm, tk, tn = tile
    assert k % tk == 0 and n % tn == 0 and tk * tn <= gm._TILE_WEIGHTS
