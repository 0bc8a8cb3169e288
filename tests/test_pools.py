"""``paged_cache.Pools`` (ISSUE 28): the page pools' format lives in one
place. The programs that touch the pools take them as ONE argument, whatever
they store; copy, export and import are one program each for both kinds."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.profiler import recompile
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
