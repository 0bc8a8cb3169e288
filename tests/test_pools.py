"""``paged_cache.Pools`` (ISSUE 28): the page pools' format lives in one
place. The programs that touch the pools take them as ONE argument, whatever
they store; copy, export and import are one program each for both kinds.

ISSUE 32 (ROADMAP S3): the pools stay where they are. The tick's layer scan
carries the stacks and its block writes and reads them by ``(layer, page[,
off])``: the compiled tick holds no pool-sized temporary, ``scatter`` and
``attend`` by layer are the per-layer functions bit for bit, and a tick
without a chunk is the tick with one, its chunk rows pad rows."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.ops.paged_attention import (paged_kv_scatter,
                                            ragged_paged_attention)
from paddle_tpu.profiler import recompile, registry
from paddle_tpu.serving import (PagePool, Pools, ServingConfig,
                                ServingEngine, SpecConfig)

KV_DTYPES = (None, "int8")


def _net(hidden=32, layers=2, seed=0):
    paddle.seed(seed)
    net = GPT(GPTConfig(vocab_size=128, hidden_size=hidden,
                        num_layers=layers, num_heads=2, max_seq_len=64))
    net.eval()
    return net


def _engine(kv_dtype, mode="plain", **kw):
    cfg = dict(num_slots=2, page_size=8, pages_per_slot=4, num_pages=17,
               prefill_chunk=8, kv_dtype=kv_dtype)
    if mode != "plain":
        cfg["spec"] = SpecConfig(draft_model=_net(16, 1, seed=1), k=2)
    if mode == "spec-sampling":
        cfg["decode"] = "sampling"
    cfg.update(kw)
    return ServingEngine(_net(), ServingConfig(**cfg))


def _prompts(lens, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 128, (t,)).astype(np.int32) for t in lens]


@pytest.mark.parametrize("mode", ["plain", "spec-greedy", "spec-sampling"])
@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_every_tick_takes_the_pools_as_one_argument(kv_dtype, mode):
    """One ``def tick`` a site: the same arguments in the same places for
    both pool kinds and both decode laws, ``Pools`` at 2, the fresh pages at
    3 (None without scales), the sampling arguments one pytree (None for
    greedy); and every site traced once over a run that mixes chunks,
    decodes and slot reuse."""
    eng = _engine(kv_dtype, mode)
    for p in _prompts((5, 12, 9)):
        eng.submit(p, 6)
    eng.run()
    counts = recompile.trace_counts()
    assert len(eng.compiled_sites) == (1 if mode == "plain" else 2)
    assert all(counts[site] == 1 for site in eng.compiled_sites)
    _, avals = eng._program_args[eng.compiled_sites[0]]
    pools, fresh = avals[2], avals[3]
    assert isinstance(pools, Pools)
    n_arrays = 4 if kv_dtype == "int8" else 2
    assert len(jax.tree.leaves(pools)) == n_arrays
    assert (fresh is None) == (kv_dtype is None)
    if mode != "plain":
        sample_args = avals[-3]
        assert (sample_args is None) == (mode == "spec-greedy")
        _, davals = eng._program_args[eng.compiled_sites[1]]
        assert (davals[-3] is None) == (mode == "spec-greedy")
    # what the unified tick takes beyond the pools does not depend on them
    if mode == "plain":
        assert len(jax.tree.leaves(avals[4:])) == 15


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_pools_flatten_to_their_arrays_and_the_pool_reads_them(kv_dtype):
    """A ``None`` scale is no leaf (so no program parameter); unflatten
    gives the tuple back; ``PagePool.k`` and friends are views of what the
    last tick stored, and nobody writes them but through ``pools``."""
    dtype = jnp.int8 if kv_dtype == "int8" else jnp.float32
    pools = Pools.zeros(2, 5, 4, 2, 8, dtype)
    leaves, treedef = jax.tree.flatten(pools)
    assert pools.quantized == (kv_dtype == "int8")
    assert [a.shape for a in leaves] == \
        [(2, 5, 4, 2, 8)] * 2 + [(2, 5, 2)] * (len(leaves) - 2)
    assert list(pools.arrays()) == \
        ["k", "v", "k_scale", "v_scale"][:len(leaves)]
    back = jax.tree.unflatten(treedef, leaves)
    assert isinstance(back, Pools) and back.k is pools.k \
        and back.v_scale is pools.v_scale
    assert pools.page_size == 4
    assert pools.reset_scales(None) is pools

    eng = _engine(kv_dtype)
    before = eng.pool.pools
    eng.submit(_prompts((5,))[0], 3)
    eng.run()
    pool = eng.pool
    assert isinstance(pool, PagePool) and pool.pools is not before
    assert pool.k is pool.pools.k and pool.v is pool.pools.v
    assert pool.k_scale is pool.pools.k_scale
    assert np.asarray(pool.k).any()         # the prompt's keys are there
    with pytest.raises(AttributeError):
        pool.k = pool.v


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_copy_export_import_move_every_array_of_a_page(kv_dtype):
    """Copy-on-write and the prefix-chain handoff through the single
    programs: a copied page equals its donor in every array, and a chain
    exported from one engine and imported into another reads back equal,
    keyed by the pools' field names."""
    src_eng = _engine(kv_dtype)
    prompt = _prompts((24,), seed=5)[0]
    src_eng.submit(prompt, 2)
    src_eng.run()
    fields = set(src_eng.pool.pools.arrays())
    assert fields == ({"k", "v", "k_scale", "v_scale"}
                      if kv_dtype == "int8" else {"k", "v"})

    payload = src_eng.export_prefix_chain(prompt)
    n = payload["n_tokens"] // src_eng.pool.page_size
    assert n == 3 and fields <= set(payload)
    assert all(payload[f].shape[1] == n for f in fields)
    assert np.asarray(payload["k"]).any()

    dst_eng = _engine(kv_dtype)
    assert dst_eng.import_prefix_chain(payload) == payload["n_tokens"]
    back = dst_eng.export_prefix_chain(prompt)
    for f in fields:
        np.testing.assert_array_equal(back[f], payload[f])
    # imported scales survive the next tick's fresh-page reset: the same
    # prompt served off the migrated chain matches the engine that wrote it
    rid = dst_eng.submit(prompt, 4)
    ref = src_eng.submit(prompt, 4)
    np.testing.assert_array_equal(dst_eng.run()[rid], src_eng.run()[ref])

    pages, _ = dst_eng.pool.prefix.chain_pages(prompt)
    donor, spare = pages[0], dst_eng.pool.allocator.alloc(1)[0]
    dst_eng.pool.pools = dst_eng._copy(dst_eng.pool.pools, np.int32(donor),
                                       np.int32(spare))
    for f, a in dst_eng.pool.pools.arrays().items():
        np.testing.assert_array_equal(np.asarray(a[:, spare]),
                                      np.asarray(a[:, donor]), err_msg=f)
    # the same three programs whatever the pools store
    assert [fn.__wrapped__ for fn in (dst_eng._copy, dst_eng._import_fn,
                                      dst_eng._export_fn)] == \
        [Pools.copy_page, Pools.write_pages, Pools.gather_pages]


# ---------------------------------------------------------------------------
# ISSUE 32: the pools are the layer scan's carry, updated in place
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_the_compiled_tick_holds_no_pool_sized_temporary(kv_dtype):
    """Pools that dwarf a toy model: the compiled unified tick's temporaries
    stay under ONE K pool's bytes and it aliases all of the donated pools (an
    xs -> ys scan kept a second stack, a ``cond`` over the pools two whole
    copies). The engine says both once, at the tick's first dispatch, and
    still compiles one site once."""
    eng = _engine(kv_dtype, num_slots=3, num_pages=4001)
    reg = registry()
    for name in ("tick_temp_bytes", "tick_alias_bytes"):
        reg.gauge("serving/" + name).set(-1.0)
    for p in _prompts((5, 12)):
        eng.submit(p, 3)
    eng.run()
    arrays = eng.pool.pools.arrays()
    one_pool = arrays["k"].nbytes
    both = sum(a.nbytes for a in arrays.values())
    weights = sum(a.nbytes for a in jax.tree.leaves(eng.served_weights()))
    assert one_pool > 4 * weights
    temp = reg.gauge("serving/tick_temp_bytes").value
    alias = reg.gauge("serving/tick_alias_bytes").value
    assert 0 <= temp < one_pool, (temp, one_pool)
    assert alias >= both, (alias, both)
    # the gauges are the compiled program's own numbers
    fn, avals = eng._program_args[eng.compiled_sites[0]]
    memory = fn.lower(*avals).compile().memory_analysis()
    assert (memory.temp_size_in_bytes, memory.alias_size_in_bytes) == \
        (temp, alias)
    assert recompile.trace_counts()[eng.compiled_sites[0]] == 1


def _mixed_tick(dtype, seed=11):
    """Stacked pools with content everywhere and one tick's rows over them:
    two decode rows, a chunk of width 4 with 3 real tokens, a pad chunk row
    (all-null table, limit 0: its writes go to the null page)."""
    layers, pages, ps, nh, hd, nps = 3, 9, 4, 2, 8, 2
    rng = np.random.default_rng(seed)
    shape = (layers, pages, ps, nh, hd)
    if dtype == jnp.int8:
        scales = [jnp.asarray(rng.uniform(0.01, 0.05, (layers, pages, nh)),
                              jnp.float32).at[:, 0].set(0.0)
                  for _ in range(2)]
        pools = Pools(*[jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                        for _ in range(2)], *scales)
    else:
        pools = Pools(*[jnp.asarray(rng.normal(size=shape), dtype)
                        for _ in range(2)])
    nd, w = 2, 4
    row_tab = np.array([[1, 2], [3, 4], [5, 6], [0, 0]], np.int32)
    row_pos0 = np.array([5, 2, 3, 0], np.int32)
    row_len = np.array([1, 1, 3, 1], np.int32)
    tok_row = np.array([0, 1] + [2] * w + [3] * w)
    tok_pos = np.array([5, 2, 3, 4, 5, 6, 0, 1, 2, 3], np.int32)
    tok_limit = np.array([8, 8, 6, 6, 6, 6, 0, 0, 0, 0], np.int32)
    page = np.where(tok_pos < tok_limit,
                    row_tab[tok_row, np.minimum(tok_pos // ps, nps - 1)], 0)
    off = tok_pos % ps
    cdtype = jnp.float32 if dtype == jnp.int8 else dtype
    kk, vv, q = (jnp.asarray(rng.normal(size=(len(tok_pos), 1, nh, hd)),
                             cdtype) for _ in range(3))
    return pools, (page.astype(np.int32), off.astype(np.int32), kk, vv), \
        (q, nd, w, row_tab, row_pos0, row_len)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=["float32", "bfloat16", "int8"])
def test_scatter_and_attend_by_layer_are_the_per_layer_functions(
        dtype, impl, attention_spelling):
    """``Pools.scatter(layer, ...)`` and ``attend(layer, ...)`` on the stacks
    against ``paged_kv_scatter`` and ``ragged_paged_attention`` on
    ``pools.k[layer]``: bit for bit, scales too, the other layers untouched,
    with the layer traced as the scan hands it over. ``attend`` names no
    spelling: it takes the one picked where it is traced."""
    attention_spelling(impl)
    pools, (page, off, kk, vv), (q, nd, w, tab, pos0, rlen) = \
        _mixed_tick(dtype)
    by_layer = jax.jit(lambda ly: pools.scatter(ly, page, off, kk, vv))

    @jax.jit
    def one_layer(k, v, ks, vs):
        k, ks = paged_kv_scatter(k, ks, page, off, kk[:, 0])
        v, vs = paged_kv_scatter(v, vs, page, off, vv[:, 0])
        return k, v, ks, vs

    def rows(attend):
        qc = q[nd:, 0].reshape(-1, w, *q.shape[2:])
        return (attend(q[:nd], tab[:nd], pos0[:nd], rlen[:nd]),
                attend(qc, tab[nd:], pos0[nd:], rlen[nd:]))

    for layer in range(pools.k.shape[0]):
        new = by_layer(np.int32(layer))
        ref = one_layer(*[None if a is None else a[layer] for a in pools])
        for name, got, was, want in zip(Pools._fields, new, pools, ref):
            if got is None:
                assert want is None
                continue
            np.testing.assert_array_equal(
                np.asarray(got[layer]), np.asarray(want), err_msg=name)
            others = np.arange(got.shape[0]) != layer
            np.testing.assert_array_equal(np.asarray(got)[others],
                                          np.asarray(was)[others],
                                          err_msg=name)
        assert np.asarray(new.k[layer] != pools.k[layer]).any()
        stacked = jax.jit(lambda ly: rows(
            lambda *a: new.attend(ly, *a)))(np.int32(layer))
        apart = jax.jit(lambda k, v, ks, vs: rows(
            lambda *a: ragged_paged_attention(
                a[0], k, v, *a[1:], impl=impl, k_scale=ks, v_scale=vs)))(
            *[None if a is None else a[layer] for a in new])
        for got, want, real in zip(stacked, apart, (rlen[:nd], rlen[nd:])):
            # pad queries (past a row's real length) are never compared
            keep = np.arange(got.shape[1])[None] < real[:, None]
            np.testing.assert_array_equal(np.asarray(got)[keep],
                                          np.asarray(want)[keep])


def _decoding_engine(kv_dtype):
    """Two slots decoding, the third free, nothing in flight."""
    eng = _engine(kv_dtype, num_slots=3, num_pages=40, prefix_cache=False)
    for p in _prompts((5, 7)):
        eng.submit(p, 20)
    for _ in range(4):
        eng.step()
    eng.drain(0)
    eng._grow_pages()               # the next position of each has its page
    assert len(eng._ticking_slots()) == 2 and eng._slot_rid.count(None) == 1
    return eng


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_a_tick_without_a_chunk_is_the_tick_with_one(kv_dtype):
    """One body, no decode-only branch: without a chunk the chunk rows ride
    as pad rows. Such a tick writes each decode row's one position and the
    null page, nothing else, and hands the decode rows the tokens that the
    same tick carrying another request's chunk hands them."""
    eng = _decoding_engine(kv_dtype)
    tick = jax.jit(eng._make_unified_tick())        # nothing donated
    ticking = eng._ticking_slots()
    args, _ = eng._build_unified([], ticking)
    before = eng.pool.pools
    after, tok, _, _ = tick(*args)

    ps = eng.pool.page_size
    wrote = {(int(eng.pool.tables[s, eng._slot_len[s] // ps]),
              int(eng._slot_len[s] % ps)) for s in ticking}
    for name in ("k", "v"):
        was, now = (np.asarray(getattr(p, name)) for p in (before, after))
        moved = (was != now).any(axis=(0, 3, 4))     # [page, offset]
        moved[0] = False                             # the null page
        if kv_dtype == "int8":
            # a page whose scale grew is requantized whole
            assert {pg for pg, _ in zip(*np.nonzero(moved))} <= \
                {pg for pg, _ in wrote}
        else:
            assert set(zip(*np.nonzero(moved))) == wrote, name
    if kv_dtype == "int8":
        for name in ("k_scale", "v_scale"):
            was, now = (np.asarray(getattr(p, name)) for p in (before, after))
            grew = set(np.nonzero((was != now).any(axis=(0, 2)))[0])
            assert grew <= {pg for pg, _ in wrote} and not now[:, 0].any()

    # the same decode rows beside a third request's first chunk
    free = eng._slot_rid.index(None)
    eng.submit(_prompts((20,), seed=9)[0], 4)
    eng._admit()
    chunks = eng._collect_chunks()
    assert [c[0] for c in chunks] == [free]
    with_chunk, _ = eng._build_unified(chunks, ticking)
    _, tok_mixed, _, _ = tick(*with_chunk)
    np.testing.assert_array_equal(np.asarray(tok)[ticking],
                                  np.asarray(tok_mixed)[ticking])
