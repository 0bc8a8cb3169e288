"""The ragged paged kernel (``ops/paged_attention._ragged_kernel``,
ISSUE 34): its work follows each row's live length. Interpreted, at toy
sizes, against the XLA spelling ``_gather_attend``; the mechanism itself
(no page past a row's last live one is ever read); the rule that picks
kernel or spelling where a program is traced; and the gauge that says
what share of the blocks at capacity a tick's rows made the kernel visit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.profiler import metrics, recompile

PS, HD = 4, 32
BLOCK = pa._BLOCK_TOKENS                 # positions of one KV block
NPS = 3 * BLOCK // PS                    # three blocks a slot
CAP = NPS * PS
#: chip_smoke.py's TOL_RAGGED: max error over the reference's max
TOL = 2e-2


def _calls():
    reg = metrics.registry()
    return {p: reg.counter("serving/attn_calls{path=%s}" % p).value
            for p in ("pallas", "xla")}


def _case(nh, t, pos0, true_len, kind, layers=None, seed=0):
    """Rows ``(pos0, true_len)`` over pools of ``kind`` ('f32', 'bf16',
    'int8'), every slot's table full of its own distinct pages, the null
    table for a row of length 0. Returns (q, k, v, meta, scales, layer)."""
    rng = np.random.RandomState(seed)
    pos0, true_len = np.asarray(pos0, np.int32), np.asarray(true_len, np.int32)
    r = len(pos0)
    pages = r * NPS + 1
    table = rng.permutation(np.arange(1, pages)).reshape(r, NPS)
    table[true_len == 0] = 0
    stack = () if layers is None else (layers,)
    shape = stack + (pages, PS, nh, HD)
    dtype = jnp.float32 if kind == "f32" else jnp.bfloat16
    q = jnp.asarray(rng.randn(r, t, nh, HD), dtype)
    scales = {}
    if kind == "int8":
        k = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        v = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        sc = rng.uniform(0.5, 1.5, (2,) + stack + (pages, nh)) / 64.0
        sc[..., 0, :] = 0.0                     # the null page's scale
        scales = dict(k_scale=jnp.asarray(sc[0], jnp.float32),
                      v_scale=jnp.asarray(sc[1], jnp.float32))
    else:
        k = jnp.asarray(rng.randn(*shape), dtype)
        v = jnp.asarray(rng.randn(*shape), dtype)
    meta = (jnp.asarray(table.astype(np.int32)), jnp.asarray(pos0),
            jnp.asarray(true_len))
    layer = None if layers is None else layers - 1
    return q, k, v, meta, scales, layer


def _attend(impl, q, k, v, meta, scales, layer):
    # the layer is traced, as inside the tick's scan
    f = jax.jit(lambda q_, k_, v_, ly: pa.ragged_paged_attention(
        q_, k_, v_, *meta, impl=impl, layer=ly, **scales))
    return np.asarray(f(q, k, v, None if layer is None else jnp.int32(layer)),
                      np.float32)


def _real(t, true_len):
    return np.arange(t)[None, :] < np.asarray(true_len)[:, None]


#: lengths 1, one under / at / one over a block's edge, capacity, and 0
def _rows(t):
    ends = [t, BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK + 1, CAP]
    true_len = [t, t, max(1, t // 2), t, 1, t, 0]
    pos0 = [max(e - n, 0) for e, n in zip(ends, true_len)] + [0]
    return pos0, true_len


@pytest.mark.parametrize("layers", [None, 3], ids=["one-layer", "stack"])
@pytest.mark.parametrize("t", [1, 5, 8], ids=["decode", "verify", "chunk"])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("nh", [2, 16], ids=["batched", "split"])
def test_kernel_matches_the_xla_spelling(nh, kind, t, layers):
    """Both ways the kernel takes the heads apart (16 heads fill bf16's
    tile: a strided load a head; 2 do not: one batched product), bf16 and
    int8 pools, decode, verify and chunk rows, one layer's pools and the
    stack with a traced layer, rows ending around every block edge."""
    pos0, true_len = _rows(t)
    case = _case(nh, t, pos0, true_len, kind, layers)
    out = _attend("pallas", *case)
    ref = _attend("xla", *case)
    real = _real(t, true_len)
    assert np.isfinite(out).all()          # pad queries and the pad row too
    assert not out[-1].any()               # a row of length 0 reads nothing
    err = np.abs(out[real] - ref[real]).max() / np.abs(ref[real]).max()
    assert err <= TOL, err


@pytest.mark.parametrize("nh", [2, 8], ids=["batched", "split"])
def test_float32_pools_agree_closely(nh):
    pos0, true_len = _rows(5)
    case = _case(nh, 5, pos0, true_len, "f32")
    real = _real(5, true_len)
    np.testing.assert_allclose(_attend("pallas", *case)[real],
                               _attend("xla", *case)[real],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("nh", [2, 8], ids=["batched", "split"])
@pytest.mark.parametrize("wide", ["queries", "pools"])
def test_mixed_types_meet_at_the_wider(nh, wide):
    """bf16 pools under float32 queries are widened in the kernel (exactly:
    no copy of a pool is made for it), float32 pools under bf16 queries
    keep their precision: either way the XLA spelling's promotion."""
    pos0, true_len = _rows(5)
    q, k, v, *rest = _case(nh, 5, pos0, true_len, "f32")
    if wide == "queries":
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    else:
        q = q.astype(jnp.bfloat16)
    real = _real(5, true_len)
    # the spelling rounds its weights to the queries' type, the kernel to
    # the products'
    tol = 2e-5 if wide == "queries" else 1e-2
    np.testing.assert_allclose(_attend("pallas", q, k, v, *rest)[real],
                               _attend("xla", q, k, v, *rest)[real],
                               rtol=tol, atol=tol)


def test_a_row_that_runs_past_its_last_page():
    """``pos0 + T`` beyond the slot's capacity: the queries inside it are
    right, the ones past it are finite."""
    t = 8
    pos0, true_len = [CAP - 3], [t]
    case = _case(16, t, pos0, true_len, "bf16")
    out, ref = _attend("pallas", *case), _attend("xla", *case)
    assert np.isfinite(out).all()
    err = np.abs(out[0, :3] - ref[0, :3]).max() / np.abs(ref[0, :3]).max()
    assert err <= TOL, err


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("nh", [2, 16], ids=["batched", "split"])
def test_nothing_past_a_rows_last_position_is_read(nh, kind):
    """The mechanism: every page past each row's last live one, and what
    lies behind the last position inside that page, filled with NaN and
    +inf (int8 pools: their scales), leaves the kernel's output as it was
    bit for bit, and finite. The capacity-wide spelling cannot pass this
    (0 x NaN)."""
    t = 5
    pos0, true_len = _rows(t)
    q, k, v, meta, scales, layer = _case(nh, t, pos0, true_len, kind)
    clean = _attend("pallas", q, k, v, meta, scales, layer)
    table = np.asarray(meta[0])
    bad = np.array([np.nan, np.inf], np.float32)
    k, v = np.array(k), np.array(v)
    sc = {n: np.array(a) for n, a in scales.items()}
    for r, (p0, n) in enumerate(zip(pos0, true_len)):
        if n == 0:
            continue
        live = min(p0 + n, CAP)
        dead_pages = table[r, -(-live // PS):]
        if kind == "int8":
            for a in sc.values():
                a[dead_pages] = bad[r % 2]
            continue
        for a in (k, v):
            a[dead_pages] = bad[r % 2]
            if live % PS:
                a[table[r, live // PS], live % PS:] = bad[(r + 1) % 2]
    sc = {n: jnp.asarray(a) for n, a in sc.items()}
    dirty = _attend("pallas", q, jnp.asarray(k), jnp.asarray(v), meta, sc,
                    layer)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)
    spelled = _attend("xla", q, jnp.asarray(k), jnp.asarray(v), meta, sc,
                      layer)
    assert not np.isfinite(spelled[_real(t, true_len)]).all()


def _traced(monkeypatch, platform, impl):
    """(counter deltas, jaxpr text) of one trace of the entry point."""
    if platform:
        monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", platform)
    q, k, v, meta, scales, _ = _case(2, 1, [3], [1], "bf16")
    before = _calls()
    text = str(jax.make_jaxpr(lambda q_, k_, v_: pa.ragged_paged_attention(
        q_, k_, v_, *meta, impl=impl))(q, k, v))
    return {p: n - before[p] for p, n in _calls().items()}, text


@pytest.mark.parametrize("platform,impl,path", [
    (None, None, "xla"),        # the CPU: the reference spelling
    ("tpu", None, "pallas"),    # traced for a TPU: the kernel
    ("tpu", "xla", "xla"),      # an explicit spelling wins
    (None, "pallas", "pallas"),
])
def test_the_platform_picks_and_an_explicit_spelling_wins(
        monkeypatch, platform, impl, path):
    calls, text = _traced(monkeypatch, platform, impl)
    other = "xla" if path == "pallas" else "pallas"
    assert calls == {path: 1, other: 0}
    assert ("pallas_call" in text) == (path == "pallas")
    assert pa.resolve_impl(impl) == path


def test_the_engines_default_is_the_platforms(monkeypatch):
    """An engine names no kernel; its tick takes the platform's where it is
    traced, and ``serving/attn_calls{path=}`` says which: the XLA spelling
    when it runs here, the kernel when the same tick is traced for a TPU
    (one call a group of rows a trace of the layer scan's body)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.serving import ServingConfig, ServingEngine

    paddle.seed(0)
    net = GPT(GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, max_seq_len=32))
    net.eval()
    assert "attention_kernel" not in ServingConfig.__dataclass_fields__
    before = _calls()
    eng = ServingEngine(net, ServingConfig(num_slots=2, page_size=4))
    eng.submit(np.arange(5, dtype=np.int32), 2)
    eng.run()
    here = {p: n - before[p] for p, n in _calls().items()}
    assert here["xla"] > 0 and here["pallas"] == 0
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    _, avals = eng._program_args[eng.compiled_sites[0]]
    # the same tick body under a site of its own: the engine's site keeps
    # its one trace (other tests count every tick site's traces)
    eng._tick_site = recompile.unique_site("serving.tick")
    before = _calls()
    text = str(jax.make_jaxpr(eng._make_unified_tick())(*avals))
    there = {p: n - before[p] for p, n in _calls().items()}
    assert there == {"pallas": here["xla"], "xla": 0}
    assert "pallas_call" in text


def test_live_block_share_is_the_hand_count():
    """Rows of 1, 256, 257 and 0 live positions and one at capacity over
    slots of three blocks: 1 + 1 + 2 + 0 + 3 of 15 blocks."""
    share = pa.live_block_share([0, BLOCK - 8, BLOCK - 7, 9, CAP - 1],
                                [1, 8, 8, 0, 8], PS, NPS)
    assert share == 7 / 15


def test_the_gauge_after_a_tick_of_known_lengths():
    """``serving/attn_live_block_share`` after each tick of one prompt of
    300 tokens through chunks of 32 over two slots of four blocks: the
    chunk row ends at ``end``, its slot's decode row sits one past it, the
    empty slot's decode row reads one position; then the decode ticks, whose
    pad chunk row (length 0) is not visited."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.serving import ServingConfig, ServingEngine

    paddle.seed(0)
    net = GPT(GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, max_seq_len=4 * BLOCK))
    net.eval()
    eng = ServingEngine(net, ServingConfig(num_slots=2, page_size=16,
                                           prefill_chunk=32))
    gauge = metrics.registry().gauge("serving/attn_live_block_share")
    eng.submit(np.arange(300, dtype=np.int32) % 64, 3)
    seen = []
    while eng.step():
        seen.append(gauge.value)
    eng.drain(0)
    blocks = lambda n: -(-n // BLOCK)
    ends = [min(start + 32, 300) for start in range(0, 300, 32)]
    want = [blocks(end) + blocks(end + 1) + 1 for end in ends]
    want += [blocks(300 + i + 1) + 1 for i in range(2)]
    assert seen == [n / (3 * 4) for n in want]
