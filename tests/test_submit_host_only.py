"""``ServingEngine.submit()`` asks nothing of the serving device (ISSUE
25): a request's default sampling key is folded on the host's CPU backend,
bit for bit what ``jax.random.fold_in`` gives anywhere, so a request never
queues behind the ticks in flight; ``serving/submit_ms`` times the call."""
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.profiler import registry
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving import engine as engine_mod

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import mp_mesh  # noqa: E402

#: the least the busy-device test keeps the serving device busy, s
BUSY_S = 2.0

#: ``fold_in`` takes 32 bits: the last id ``_next_rid`` can hand out
LAST_RID = 2 ** 32 - 1


def toy_engine(**kw):
    paddle.seed(0)
    net = GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=64))
    net.eval()
    return ServingEngine(net, ServingConfig(num_slots=2, page_size=16, **kw))


def default_key(eng, rid):
    """The key ``submit()`` gives request ``rid`` when none is passed."""
    eng._next_rid = rid
    assert eng.submit(np.arange(5, dtype=np.int32), 2) == rid
    return eng._requests[rid].key


@pytest.fixture(scope="module", params=[0, 5, 2 ** 31 + 7])
def seeded(request):
    return request.param, toy_engine(seed=request.param)


@pytest.mark.parametrize("rid", [0, 1, 12, 2 ** 20 + 3, 2 ** 31, LAST_RID])
def test_the_default_key_is_fold_in_of_the_seed_and_the_rid(seeded, rid):
    seed, eng = seeded
    want = np.asarray(jax.random.fold_in(jax.random.PRNGKey(seed), rid))
    got = default_key(eng, rid)
    assert got.dtype == np.uint32 and got.tolist() == want.tolist()


def test_the_default_key_follows_the_configured_prng():
    with jax.default_prng_impl("rbg"):
        want = np.asarray(jax.random.fold_in(jax.random.PRNGKey(3), 12))
        got = default_key(toy_engine(seed=3), 12)
    assert want.shape == (4,) and got.tolist() == want.tolist()


def test_a_rid_past_32_bits_is_refused_as_fold_in_refuses_it():
    with pytest.raises(OverflowError):
        jax.random.fold_in(jax.random.PRNGKey(0), LAST_RID + 1)
    with pytest.raises(OverflowError):
        default_key(toy_engine(), LAST_RID + 1)


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_a_sampled_run_draws_the_same_tokens_from_default_and_passed_keys(
        spec):
    kw = dict(decode="sampling", temperature=0.8, seed=11)
    if spec:
        from paddle_tpu.serving import SpecConfig

        paddle.seed(1)
        draft = GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=1,
                              num_heads=2, max_seq_len=64))
        draft.eval()
        kw["spec"] = SpecConfig(draft, k=2)
    prompts = [np.arange(3 + 2 * i, dtype=np.int32) + i for i in range(4)]
    base = jax.random.PRNGKey(11)

    def run(keys):
        eng = toy_engine(**kw)
        rids = [eng.submit(p, 12, key=k) for p, k in zip(prompts, keys)]
        out = eng.run()
        return [out[r].tolist() for r in rids]

    passed = run([np.asarray(jax.random.fold_in(base, i)) for i in range(4)])
    assert run([None] * 4) == passed
    # the keys are read: another seed's keys draw other tokens
    other = jax.random.PRNGKey(12)
    assert run([np.asarray(jax.random.fold_in(other, i))
                for i in range(4)]) != passed


@jax.jit
def _busy_program(x, steps):
    # a chain of small steps: all of one device's queue for as long as
    # ``steps`` says, and little of the host's thread pool
    return jax.lax.fori_loop(0, steps, lambda _, a: jnp.tanh(a @ a), x)


def test_submit_does_not_queue_behind_a_busy_serving_device():
    """The engine lives on the second virtual CPU device, and that device
    is given work (the ticks in flight) that lasts a thousand ``submit()``s
    and at least ``BUSY_S``, by this host's measured speed. Anything
    ``submit()`` waited for on that device's queue would come after the
    work, which would then be done when ``submit()`` returns: the
    device-side fold failed exactly so."""
    host_devices = jax.local_devices(backend="cpu")
    if len(host_devices) < 2:
        pytest.skip("needs a second CPU device to serve from")
    serving_device = host_devices[1]
    with jax.default_device(serving_device):
        eng = toy_engine()
        # the fold runs where the base key was committed, not on the default
        folded = engine_mod._fold_key(eng._base_key, np.uint32(1))
        assert folded.devices() == eng._base_key.devices() == {host_devices[0]}
        submit_s = 0.0
        for n in range(3):
            began = time.perf_counter()
            eng.submit(np.arange(5 + n, dtype=np.int32), 2)
            submit_s = max(submit_s, time.perf_counter() - began)
        x = jnp.full((8, 8), 0.01, jnp.float32)
        _busy_program(x, np.int32(10)).block_until_ready()      # compiled
        began = time.perf_counter()
        _busy_program(x, np.int32(20_000)).block_until_ready()
        steps_per_s = 20_000 / (time.perf_counter() - began)
        steps = int(steps_per_s * max(BUSY_S, 1000 * submit_s))
        busy = _busy_program(x, np.int32(steps))
        assert busy.devices() == {serving_device} and not busy.is_ready()
        rid = eng.submit(np.arange(7, dtype=np.int32), 3)
        still_running = not busy.is_ready()
        busy.block_until_ready()
    assert still_running
    want = jax.random.fold_in(jax.random.PRNGKey(0), rid)
    assert eng._requests[rid].key.tolist() == np.asarray(want).tolist()


def test_every_rank_of_a_mesh_folds_on_its_own_cpu_device(tmp_path):
    """Two real processes under ``jax.distributed``: the global device list
    starts with rank 0's CPU device, which rank 1 cannot address."""
    if not mp_mesh.can_spawn():
        pytest.skip("cannot spawn worker processes here")
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "multihost", "worker_submit.py")
    res = mp_mesh.launch(2, worker, [str(tmp_path)],
                         log_dir=str(tmp_path / "logs"), timeout=240)
    assert res.ok, res.tail()


def test_a_process_without_the_cpu_backend_is_told_what_to_set(monkeypatch):
    def no_cpu(*a, backend=None, **kw):
        raise RuntimeError(f"Unknown backend {backend}")

    monkeypatch.setattr(jax, "local_devices", no_cpu)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=tpu,cpu"):
        toy_engine()


def test_submit_compiles_nothing_after_the_engine_is_built(caplog):
    def compiled():
        return [r.getMessage() for r in caplog.records
                if "Finished XLA compilation" in r.getMessage()]

    eng = toy_engine(seed=4)
    with jax.log_compiles():
        for n in range(3):
            eng.submit(np.arange(5 + n, dtype=np.int32), 2)
        assert compiled() == []
        jax.jit(lambda a: a * 3 + 1)(np.float32(2))     # the log does see one
        assert len(compiled()) == 1


def test_submit_ms_takes_one_sample_a_submit():
    hist = registry().histogram("serving/submit_ms")
    eng = toy_engine()
    n0 = hist.count
    for n in range(3):
        eng.submit(np.arange(5 + n, dtype=np.int32), 2)
    eng.submit(np.arange(5, dtype=np.int32), 2, key=np.zeros(2, np.uint32))
    assert hist.count - n0 == 4
    assert 0 < hist.snapshot()["max"] < 1000.0
    with pytest.raises(ValueError):
        eng.submit(np.zeros(0, np.int32), 2)            # nothing was queued
    assert hist.count - n0 == 4
