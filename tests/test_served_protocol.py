"""The seam between the serving engine, a served model and its caches
(ISSUE 43; ``models/tick.py`` states it): every served model gives
``cache_spec()``, ``_decode_state()`` and ``ragged_apply(...) -> (logits,
pools, aux)``; the engine asks nothing else of it and keeps no fallback; the
pool is built from the spec by ``paged_cache.page_pool``; ``LatentPools``'
methods are the only code that names its fields; and no configuration, engine
or forward names an attention kernel."""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.models.deepseek_v2 import DeepseekV2, DeepseekV2Config
from paddle_tpu.models.dots3 import Dots3, Dots3Config
from paddle_tpu.models.tick import LoopRecord, TickRecord
from paddle_tpu.ops import latent_attention as pa
from paddle_tpu.profiler import recompile
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.paged_cache import (LatentPagePool, LatentPools,
                                            PagePool, Pools, page_pool)

PAGE = 4


def _gpt(**kw):
    return GPT(GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                         num_heads=2, max_seq_len=64, **kw))


MODELS = {
    "gpt3": lambda: _gpt(),
    "looped": lambda: _gpt(loop_steps=3, norm="rmsnorm", position="rope",
                           bias=False, ffn="swiglu",
                           tie_word_embeddings=False),
    "dots3": lambda: Dots3(Dots3Config.tiny(experts_held=(0, 4))),
    "deepseek_v2": lambda: DeepseekV2(DeepseekV2Config.tiny(
        experts_held=(0, 4))),
}
#: the record each model's ``cache_spec()`` names (None: it reports nothing)
RECORDS = {"gpt3": None, "looped": LoopRecord, "dots3": TickRecord,
           "deepseek_v2": TickRecord}


@pytest.mark.parametrize("name", list(MODELS))
def test_a_served_model_gives_the_three_methods_and_is_served(name):
    paddle.seed(3)
    net = MODELS[name]()
    net.eval()
    spec = net.cache_spec()
    assert isinstance(spec, dict) and spec["kind"] in ("kv", "latent")
    record = spec.get("tick_record")
    assert record is None if RECORDS[name] is None \
        else issubclass(record, RECORDS[name])
    stacked, other = net._decode_state()
    assert net._decode_state()[0] is stacked        # kept until a weight moves

    eng = ServingEngine(net, ServingConfig(
        num_slots=2, page_size=PAGE, pages_per_slot=8, prefill_chunk=8,
        prefix_cache=False))
    assert isinstance(eng.pool, LatentPagePool) == (spec["kind"] == "latent")
    assert (eng.tick_record is None) == (record is None)
    # one tick's arguments as the engine builds them, through the forward
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(0, 96, n).astype(np.int32), 4)
            for n in (5, 11)]
    eng._admit()
    chunks = eng._collect_chunks()
    (_, _, pools, _, last_tok, pf_toks, tok_pos, tok_limit, row_tab,
     row_pos0, row_len, sample_ix, *_), _ = eng._build_unified(
        chunks, eng._ticking_slots())
    logits, pools_out, aux = net.ragged_apply(
        stacked, other, pools, jnp.concatenate([last_tok, pf_toks]),
        tok_pos, tok_limit, row_tab, row_pos0, row_len, sample_ix,
        decode_rows=2, chunk_width=8, has_chunks=np.bool_(True))
    assert logits.shape == (2, 96)
    assert type(pools_out) is type(pools)
    assert isinstance(aux, dict) and bool(aux) == (record is not None)
    assert all(isinstance(a, jax.Array) for a in aux.values())
    # and two requests served through it, the tick traced once
    out = eng.run()
    assert [len(out[r]) for r in rids] == [4, 4]
    assert recompile.trace_counts()[eng.compiled_sites[0]] == 1
    n = eng.exit_steps(rids[0])[2]
    assert n == 4
    if name == "looped":
        assert 1.0 <= eng.exit_steps(rids[1])[0] <= 3.0
        assert eng.exit_steps()[2] == 8 and eng.exit_steps()[2] == 0
    else:
        assert eng.exit_steps() == (0.0, 0.0, 0)


_SRC = os.path.join(os.path.dirname(__file__), os.pardir, "paddle_tpu")
#: what ISSUE 43 took out of each file, as a pattern over its source
_ENGINE = (r"_latent\b", r"getattr\(model", r"getattr\(mcfg",
           r"attention_kernel", r"_impl\b", r"models\.gpt", r"LatentPagePool")
_MODEL = (r"\.index_k", r"\bpl\.(latent|window)", r"pools\.(latent|window)\b",
          r"_replace\(", r"latent_scatter\(", r"_pa\.", r"attention_kernel",
          r"impl=", r"ops import (paged|latent)_attention")


@pytest.mark.parametrize("path,banished", [
    ("serving/engine.py", _ENGINE), ("models/gpt.py", _MODEL),
    ("models/dots3.py", _MODEL + (r"^from \.gpt import .*_rms",)),
    ("models/deepseek_v2.py", _MODEL + (r"import dots3|_d3\.",)),
    ("models/tick.py", _MODEL + (r"^from \.(gpt|dots3)",)),
])
def test_the_banished_names_stay_out(path, banished):
    with open(os.path.join(_SRC, path)) as f:
        source = f.read()
    for pattern in banished:
        found = re.findall(pattern, source, re.M)
        assert not found, f"{path} holds {pattern!r}: {found[:3]}"


def _latent_pools(rng):
    shape = lambda layers, width: (layers, 9, width, PAGE)      # noqa: E731
    return LatentPools(*(jnp.asarray(rng.normal(size=s), jnp.float32)
                         for s in (shape(2, 10), shape(2, 6), shape(3, 8))))


def _same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("field", ["latent", "index_k", "window"])
def test_latent_pools_methods_are_the_ops_on_their_field(field):
    """Each method of ``LatentPools`` is ``ops/latent_attention``'s function
    on the field it is for, bit for bit, the other fields untouched: the
    write, then every read of that field over what was written."""
    rng = np.random.default_rng(7)
    pools = _latent_pools(rng)
    layer = 1
    nh, pos0, t = 2, np.asarray([6, 3]), 3
    n = 2 * t
    table = np.asarray([[3, 7, 1, 0], [5, 2, 0, 0]], np.int32)
    tok_pos = (pos0[:, None] + np.arange(t)[None]).reshape(-1)
    page = table[np.repeat(np.arange(2), t), tok_pos // PAGE]
    off = tok_pos % PAGE
    touched = np.unique(page)
    meta = (table, pos0, np.full(2, t))
    pool = getattr(pools, field)
    width = pool.shape[2]
    rows = jnp.asarray(rng.normal(size=(n, width)), jnp.float32)
    write = {"latent": pools.scatter_latent, "index_k": pools.scatter_index,
             "window": pools.scatter_window}[field]
    wrote = write(layer, page, off, rows, touched)
    want = pa.latent_scatter(pool, page, off, rows, layer, touched)
    _same(wrote, pools._replace(**{field: want}))
    assert np.asarray(want[layer] != pool[layer]).any()

    q = jnp.asarray(rng.normal(size=(2, t, nh, width)), jnp.float32)
    if field == "index_k":
        w_i = jnp.asarray(rng.normal(size=(2, t, nh)), jnp.float32)
        _same(wrote.index_scores(layer, q, w_i, *meta),
              pa.index_scores(q, w_i, want, layer, *meta))
    elif field == "window":
        _same(wrote.attend_window(layer, q, *meta, 5, width - 2, 0.3),
              pa.window_latent_attention(q, want, layer, *meta, 5,
                                         width - 2, 0.3))
    else:
        score = jnp.asarray(rng.normal(size=(n, 4 * PAGE)), jnp.float32)
        keys, thr, ties = pa.select_threshold(score, 5)
        sel = (keys.reshape(2, t, -1), thr.reshape(2, t), ties.reshape(2, t))
        _same(wrote.attend_selected(layer, q, *meta, *sel, width - 2, 0.3),
              pa.selected_latent_attention(q, want, layer, *meta, *sel,
                                           width - 2, 0.3))
        _same(wrote.attend(layer, q, *meta, width - 2, 0.3),
              pa.latent_attention(q, want, layer, *meta, width - 2, 0.3))


_KV = {"kind": "kv", "layers": 6, "heads": 2, "head_dim": 16}
_LATENT = {"kind": "latent", "full_layers": 3, "latent_width": 12}
_WINDOWED = dict(_LATENT, index_width=8, window_layers=2, window_width=16,
                 window=5)


@pytest.mark.parametrize("spec", [_KV, _LATENT, _WINDOWED],
                         ids=["kv", "latent", "latent+window"])
def test_the_pool_is_built_from_the_spec(spec):
    pool = page_pool(spec, 41, PAGE, 3, 8, 8, jnp.float32, False, False)
    pool.grow_slot(0, 4)
    if spec["kind"] == "kv":
        assert type(pool) is PagePool and isinstance(pool.pools, Pools)
        assert pool.pools.k.shape == (6, 41, PAGE, 2, 16)
        assert pool.row_tables([0, None]).shape == (2, 8)
        # K and V pools do all an engine asks of a pool
        page_pool(spec, 41, PAGE, 3, 8, 8, jnp.int8, True, True).require(
            "handoff", "export_held (a KV handoff)")
    else:
        assert type(pool) is LatentPagePool
        windowed = spec.get("window_layers", 0)
        assert pool.pools.latent.shape == (3, 41, 12, PAGE)
        assert pool.pools.index_k.shape[2] == spec.get("index_width", 0)
        assert pool.pools.window.shape[0] == windowed
        assert len(pool.row_tables([0, None])) == 2
        assert set(pool.live_shares()) == (
            {"latent", "window"} if windowed else {"latent"})
        # what it cannot do it refuses in its own words, before it allocates
        with pytest.raises(NotImplementedError, match="verify tick"):
            page_pool(spec, 41, PAGE, 3, 8, 8, jnp.float32, False, True)
        with pytest.raises(NotImplementedError, match="no head axis"):
            page_pool(spec, 41, PAGE, 3, 8, 8, jnp.int8, False, False)
        with pytest.raises(NotImplementedError, match="export_held.*K and V"):
            pool.require("handoff", "export_held (a KV handoff)")
    # a window's pages behind the frontier go back; any other pool frees none
    assert pool.free_behind(0, 14) == (2 if spec.get("window_layers") else 0)
    assert pool.check_consistency() == []


# --- TickRows.dense: the row-wise stretches of a tick (ISSUE 55) -----------
@pytest.mark.parametrize("has_chunks", [None, True, False])
def test_tick_rows_dense_is_fn_over_the_rows_that_exist(has_chunks):
    """Not told whether a chunk rides (``None``) ``dense`` is ``fn`` over
    all rows and no ``cond``; told there is one, the same values under a
    ``cond``; told there is none, ``fn`` over the decode rows and zeros for
    the chunk rows' pad tokens, in one shape."""
    from paddle_tpu.models.tick import TickRows

    nd, w = 3, 4
    nt = nd + w
    pos = jnp.arange(nt, dtype=jnp.int32)
    x = jnp.arange(nt * 2, dtype=jnp.float32).reshape(nt, 2) + 1.0
    m = jnp.asarray([[1.0, 2.0, 3.0], [0.5, -1.0, 0.0]])

    def fn(x, pos):
        return {"y": x @ m, "p": (pos + 1)[:, None] * x}

    def stretch(x, pos, told):
        rows = TickRows(PAGE, 2, pos, pos, pos[:nd + 1], nt, nd, w, told)
        return rows.dense(fn, x, pos)

    told = None if has_chunks is None else jnp.asarray(has_chunks)
    got = stretch(x, pos, told)
    want = fn(x, pos)
    if has_chunks is False:
        want = jax.tree.map(lambda a: a.at[nd:].set(0), want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key])
    # an array as its groups' parts (what the pools leave) is joined inside
    in_parts = stretch([x[:nd], x[nd:]], pos, told)
    for key in want:
        np.testing.assert_array_equal(in_parts[key], want[key])
    text = str(jax.make_jaxpr(lambda x, pos, t: stretch(x, pos, t))(
        x, pos, jnp.asarray(True)))
    assert " cond[" in text
    assert " cond[" not in str(jax.make_jaxpr(
        lambda x, pos: stretch(x, pos, None))(x, pos))
    # one group alone (a forward of a whole sequence: no decode row): no cond
    rows = TickRows(PAGE, 2, pos[:w], pos[:w], pos[:1], w, 0, w,
                    jnp.asarray(False))
    np.testing.assert_array_equal(rows.dense(fn, x[:w], pos[:w])["y"],
                                  x[:w] @ m)
