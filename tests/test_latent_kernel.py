"""The latent-attention kernel (``ops/latent_attention._latent_kernel``,
ISSUE 39): interpreted, at toy sizes, against the XLA spelling
``_selected_latent_xla`` and against a dense softmax under
``selection_mask``; the mechanism (no page past a tile's last visible
position is read); the selection's rule, ties included; and the rule that
picks kernel or spelling where a program is traced.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import latent_attention as pa
from paddle_tpu.profiler import metrics

PS, NH, W, C = 4, 4, 24, 16
BLOCK = 16                      # positions of one block here: four pages
NPS = 10                        # two and a half blocks a slot
CAP = NPS * PS
TOPK = 6
SCALE = 0.3
TOL = 2e-2
TILE_ROWS = pa._LATENT_TILE_ROWS        # the chip's, before ``small_blocks``


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of four pages of 4 and tiles of 8 queries x 4 heads, so that
    toy rows cross blocks and tiles."""
    monkeypatch.setattr(pa, "_LATENT_BLOCK_TOKENS", BLOCK)
    monkeypatch.setattr(pa, "_LATENT_TILE_ROWS", 8 * NH)


def _case(pos0, true_len, t, dtype=jnp.bfloat16, seed=0, scores=None,
          topk=TOPK, layers=2):
    """Rows ``(pos0, true_len)`` of ``t`` queries over a latent pool, every
    live slot's table full of its own pages in a shuffled order, the null
    table for a row of length 0; the selection from seeded indexer scores
    (``scores(rng, r, t)`` to plant them) through ``select_threshold``."""
    rng = np.random.RandomState(seed)
    pos0, true_len = np.asarray(pos0, np.int32), np.asarray(true_len, np.int32)
    r = len(pos0)
    pages = r * NPS + 1
    table = rng.permutation(np.arange(1, pages)).reshape(r, NPS)
    table[true_len == 0] = 0
    pool = jnp.asarray(rng.randn(layers, pages, W, PS), dtype)
    q = jnp.asarray(rng.randn(r, t, NH, W), dtype)
    live = np.where(true_len > 0, np.minimum(pos0 + true_len, CAP), 0)
    qpos = pos0[:, None] + np.arange(t)[None, :]
    last = np.minimum(qpos, live[:, None] - 1)
    seen = np.arange(CAP)[None, None, :] <= last[:, :, None]
    sc = rng.randn(r, t, CAP).astype(np.float32) if scores is None \
        else scores(rng, r, t)
    sc = np.where(seen, sc, -np.inf).astype(np.float32)
    keys, thr, ties = pa.select_threshold(jnp.asarray(sc.reshape(r * t, CAP)),
                                          topk)
    sel = (keys.reshape(r, t, CAP), thr.reshape(r, t), ties.reshape(r, t))
    meta = (jnp.asarray(table.astype(np.int32)), jnp.asarray(pos0),
            jnp.asarray(true_len))
    return q, pool, meta, sel, seen


def _attend(impl, q, pool, meta, sel, layer=1):
    # the layer is traced, as inside a tick's scan
    f = jax.jit(lambda q_, pool_, ly: pa.selected_latent_attention(
        q_, pool_, ly, *meta, *sel, C, SCALE, impl=impl))
    return np.asarray(f(q, pool, jnp.int32(layer)), np.float32)


def _dense(q, pool, meta, sel, seen, layer=1):
    """Float32 softmax over whole rows under ``selection_mask``."""
    table = np.asarray(meta[0])
    r, t = q.shape[:2]
    flat = np.swapaxes(np.asarray(pool, np.float32)[layer][table], 2, 3)
    flat = np.nan_to_num(flat.reshape(r, CAP, W), nan=0.0, posinf=0.0)
    keys, thr, ties = sel
    keep = np.asarray(pa.selection_mask(
        keys.reshape(r * t, CAP), thr.reshape(-1), ties.reshape(-1))
    ).reshape(r, t, CAP) & seen
    s = np.einsum("rtnc,rsc->rtns", np.asarray(q, np.float32), flat) * SCALE
    s = np.where(keep[:, :, None], s, -np.inf)
    with np.errstate(invalid="ignore"):
        p = np.exp(s - np.max(s, -1, keepdims=True))
    p = np.where(keep[:, :, None], p, 0.0)
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-30)
    return np.einsum("rtns,rsc->rtnc", p, flat[..., :C]), keep


def _real(t, true_len):
    return np.arange(t)[None, :] < np.asarray(true_len)[:, None]


def _tied(levels):
    """Scores drawn from ``levels`` values, so that every selection's
    threshold is tied many times over, in blocks on either side of it."""
    def scores(rng, r, t):
        return rng.randint(0, levels, (r, t, CAP)).astype(np.float32)
    return scores


#: (rows' pos0, true_len, queries a row, planted scores, top-k)
CASES = {
    # twelve decode rows' worth in small: rows of one query, lengths that
    # end under, at and over a block's edge, and a free slot
    "decode rows": ([0, BLOCK - 2, BLOCK - 1, BLOCK, 2 * BLOCK + 3, 0],
                    [1, 1, 1, 1, 1, 0], 1, None, TOPK),
    # what one call of the tick's chunk group carries, and what its decode
    # group does, both in one call: rows of 16 with 1 real query beside
    # whole chunks
    "decode rows and a chunk row": ([5, CAP - 17, 20], [1, 16, 1], 16, None,
                                    TOPK),
    "a chunk that straddles pages and blocks": ([BLOCK - 3], [16], 16, None,
                                                TOPK),
    "a chunk from position 0": ([0], [16], 16, None, TOPK),
    "a short last chunk: pad tiles": ([18], [5], 16, None, TOPK),
    # fewer visible positions than top-k: everything visible is taken
    "fewer visible than top-k": ([0, 2], [4, 1], 8, None, 12),
    "ties at the threshold across blocks": ([CAP - 16, 3], [16, 16], 16,
                                            _tied(3), TOPK),
    "every score equal": ([CAP - 8], [8], 8, _tied(1), TOPK),
    "a free slot beside live ones": ([7, 0, 30, 0], [8, 0, 8, 0], 8, None,
                                     TOPK),
    "rows of very different lengths": ([0, CAP - 8, 1, BLOCK], [8, 8, 2, 7],
                                       8, None, TOPK),
    "a row that runs past its last page": ([CAP - 3], [8], 8, None, TOPK),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_the_xla_spelling(name, dtype):
    """The kernel's output is the spelling's for every real query (and a
    dense float32 softmax under ``selection_mask``: the kernel's selection
    is ``select_threshold``'s, ties included), finite for pad queries and
    zero for a row that holds nothing."""
    pos0, true_len, t, scores, topk = CASES[name]
    q, pool, meta, sel, seen = _case(pos0, true_len, t, dtype, scores=scores,
                                     topk=topk)
    out = _attend("pallas", q, pool, meta, sel)
    ref = _attend("xla", q, pool, meta, sel)
    want, keep = _dense(q, pool, meta, sel, seen)
    real = _real(t, true_len)
    # inside the slot's capacity (a query past it has no position)
    real &= (np.asarray(pos0)[:, None] + np.arange(t)[None, :]) < CAP
    assert real.any() and keep[real].any(-1).all()
    assert np.isfinite(out).all()
    assert not out[np.asarray(true_len) == 0].any()
    tol = TOL if dtype == jnp.bfloat16 else 2e-5
    for other in (ref[real], want[real]):
        err = np.abs(out[real] - other).max() / np.abs(other).max()
        assert err <= tol, err


def test_a_tie_moved_changes_the_output():
    """The test above can tell a tie taken from one not taken: with every
    score equal the selection is the first ``TOPK`` visible positions, and
    taking the last ones instead is another answer."""
    pos0, true_len, t, scores, topk = CASES["every score equal"]
    q, pool, meta, (keys, thr, ties), seen = _case(
        pos0, true_len, t, jnp.float32, scores=scores, topk=topk)
    out = _attend("pallas", q, pool, meta, (keys, thr, ties))
    want, keep = _dense(q, pool, meta, (keys, thr, ties), seen)
    assert (keep.sum(-1) == TOPK).all() and keep[0, :, :TOPK].all()
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    moved = _attend("pallas", q, pool, meta, (keys, thr, ties + 1))
    assert np.abs(moved - want).max() > 1e-3


def test_a_query_with_nothing_selected_gets_zeros():
    """A threshold above every key: nothing is kept in any block."""
    q, pool, meta, (keys, thr, ties), _ = _case([20], [8], 8, jnp.float32)
    none = (keys, jnp.full_like(thr, 2 ** 32 - 1), jnp.zeros_like(ties))
    for impl in ("pallas", "xla"):
        assert not _attend(impl, q, pool, meta, none).any()


def test_nothing_past_a_tiles_last_position_is_read():
    """The mechanism: every page past each row's last live one, and what
    lies behind the last position inside that page, filled with NaN and
    +inf, leaves the kernel's output as it was bit for bit, and finite."""
    pos0, true_len, t = [0, BLOCK - 3, 2 * BLOCK + 1, 9], [8, 8, 3, 0], 8
    q, pool, meta, sel, _ = _case(pos0, true_len, t)
    clean = _attend("pallas", q, pool, meta, sel)
    table = np.asarray(meta[0])
    dirty = np.array(pool.astype(jnp.float32))
    bad = np.array([np.nan, np.inf], np.float32)
    dirty[:, 0] = np.nan                    # the null page
    for r, (p0, n) in enumerate(zip(pos0, true_len)):
        live = min(p0 + n, CAP) if n else 0
        dirty[:, table[r, -(-live // PS):]] = bad[r % 2]
        if live % PS:
            dirty[:, table[r, live // PS], :, live % PS:] = bad[(r + 1) % 2]
    out = _attend("pallas", q, jnp.asarray(dirty, pool.dtype), meta, sel)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, clean)


def test_a_tile_walks_no_further_than_its_last_query_sees():
    """Of a chunk of 16 at position 0 the first tile of 8 sees positions
    0-7: the pages that hold 8-15, which the second tile reads, may hold
    anything as far as the first tile's queries go."""
    q, pool, meta, sel, _ = _case([0], [16], 16)
    clean = _attend("pallas", q, pool, meta, sel)
    table = np.asarray(meta[0])
    dirty = np.array(pool.astype(jnp.float32))
    dirty[:, table[0, 2:]] = np.nan         # positions 8 and up
    out = _attend("pallas", q, jnp.asarray(dirty, pool.dtype), meta, sel)
    np.testing.assert_array_equal(out[0, :8], clean[0, :8])
    # the second tile did read them: its queries' answers are gone
    assert not np.allclose(out[0, 8:], clean[0, 8:], equal_nan=True)


@pytest.mark.parametrize("ties", [-3, 0, 1, 2, 5, 40])
def test_the_last_taken_tie_is_selection_masks(ties):
    """``_last_taken_tie`` against the running count it stands for."""
    rng = np.random.RandomState(ties + 3)
    keys = jnp.asarray(rng.randint(0, 3, (2, 3, CAP)), jnp.uint32)
    thr = jnp.ones((2, 3), jnp.uint32)
    last = jnp.asarray(rng.randint(-1, CAP, (2, 3)), jnp.int32)
    n = jnp.full((2, 3), ties, jnp.int32)
    cut = np.asarray(pa._last_taken_tie(keys, thr, n, last, PS))
    kpos = np.arange(CAP)
    seen = kpos[None, None] <= np.asarray(last)[..., None]
    tie = (np.asarray(keys) == 1) & seen
    want = tie & (np.cumsum(tie, -1) <= ties)
    np.testing.assert_array_equal(tie & (kpos <= cut[..., None]), want)


def _calls():
    reg = metrics.registry()
    return {p: reg.counter("serving/latent_attn_calls{path=%s}" % p).value
            for p in ("pallas", "xla")}


@pytest.mark.parametrize("platform,impl,shape,path", [
    (None, None, "cell", "xla"),        # the CPU: the reference spelling
    ("tpu", None, "cell", "pallas"),    # traced for a TPU: the kernel
    ("tpu", None, "toy", "xla"),        # ... unless Mosaic cannot tile it
    ("tpu", "xla", "cell", "xla"),      # an explicit spelling wins
    (None, "pallas", "toy", "pallas"),
])
def test_platform_and_shapes_pick_and_an_explicit_spelling_wins(
        monkeypatch, platform, impl, shape, path):
    if platform:
        monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", platform)
    nh, w, c, ps = (128, 576, 512, 128) if shape == "cell" else (NH, W, C, PS)
    if shape == "cell":
        monkeypatch.setattr(pa, "_LATENT_TILE_ROWS", TILE_ROWS)
    q = jax.ShapeDtypeStruct((1, 8, nh, w), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((1, 3, w, ps), jnp.bfloat16)
    assert pa.latent_attention_path(q, pool, c, impl) == path
    if shape == "toy":
        q, pool, meta, sel, _ = _case([3], [8], 8)
        before = _calls()
        text = str(jax.make_jaxpr(
            lambda q_, p_: pa.selected_latent_attention(
                q_, p_, 0, *meta, *sel, C, SCALE, impl=impl))(q, pool))
        other = "xla" if path == "pallas" else "pallas"
        assert {p: n - before[p] for p, n in _calls().items()} \
            == {path: 1, other: 0}
        assert ("pallas_call" in text) == (path == "pallas")


@pytest.mark.parametrize("plen", [7, 23])
def test_an_engine_told_to_take_the_kernel_serves_the_same_tokens(
        plen, attention_spelling):
    """The full layers' attention is picked where the tick is traced
    (``latent_attention_path``): an engine whose ticks are traced with the
    kernel picked runs it (interpreted here) in every full layer of every
    tick, and emits what the XLA spelling's engine emits, the same top
    logits within float32's reassociation."""
    from paddle_tpu.models.dots3 import Dots3, Dots3Config
    from paddle_tpu.serving import ServingConfig, ServingEngine

    import paddle_tpu as paddle

    paddle.seed(0)
    net = Dots3(Dots3Config.tiny(experts_held=(0, 4)))
    net.eval()
    prompt = np.random.default_rng(0).integers(0, 96, plen).astype(np.int32)
    outs, tops = {}, {}
    for path in ("xla", "pallas"):
        attention_spelling(path)
        eng = ServingEngine(net, ServingConfig(
            num_slots=3, page_size=4, pages_per_slot=16, prefix_cache=False))
        before = _calls()
        rid = eng.submit(prompt, 10)
        outs[path] = eng.run()[rid].tolist()
        tops[path] = eng.tick_record.top_logits(rid)
        took = {p: n - before[p] for p, n in _calls().items()}
        assert took[path] > 0 and sum(took.values()) == took[path]
    assert outs["pallas"] == outs["xla"]
    np.testing.assert_allclose(tops["pallas"], tops["xla"], atol=5e-5)
