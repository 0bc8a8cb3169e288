"""Quantized-collective tests (ISSUE 12, distributed/qcomm.py):
blockwise int8 round-trip units, the EQuARX-style compressed AllReduce
vs f32 psum on the virtual 8-device CPU mesh, loss-curve parity of
quantized-DP training, and the collective-byte accounting showing the
≤ 0.55x wire-byte bound (with the per-dtype gauges the profiler
satellite added). Heavy legs (the pipeline-trainer variant) are
slow-marked per the saturated-cap rule; the tier-1 legs use a micro
GPT so the two trainer compiles stay cheap."""
import functools

import numpy as np
import pytest

import jax

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.distributed import qcomm  # noqa: E402
from paddle_tpu.distributed.fleet import DistributedStrategy  # noqa: E402
from paddle_tpu.distributed.mesh import create_mesh  # noqa: E402
from paddle_tpu.distributed.strategy_compiler import (  # noqa: E402
    build_mesh_from_strategy, compile_train_step)
from paddle_tpu.models import GPT, GPTConfig  # noqa: E402

shard_map = functools.partial(jax.shard_map, check_vma=False)

N_DEV = len(jax.devices())
needs_mesh = pytest.mark.skipif(N_DEV < 8,
                                reason="needs the 8-device CPU mesh")


def _micro_gpt():
    paddle.seed(3)
    net = GPT(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=32))
    return net


def _trainer(dp_grad_comm, **kw):
    net = _micro_gpt()
    opt = paddle.optimizer.AdamW(2e-3, parameters=net.parameters())
    s = DistributedStrategy()
    mesh = build_mesh_from_strategy(s)
    return compile_train_step(net, opt, s, mesh,
                              dp_grad_comm=dp_grad_comm, **kw)


class TestQuantizeBlockwise:
    def test_roundtrip_bound(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(1024).astype(np.float32) * 5)
        q, s = qcomm.quantize_blockwise(x, block=128)
        back = qcomm.dequantize_blockwise(q, s, block=128)
        # error per element <= half a quantization step of ITS block
        step = np.repeat(np.asarray(s), 128)
        assert np.all(np.abs(np.asarray(back - x)) <= step / 2 + 1e-7)

    def test_zero_block_exact(self):
        x = jnp.zeros(256, jnp.float32)
        q, s = qcomm.quantize_blockwise(x, block=128)
        assert float(jnp.abs(s).max()) == 0.0
        assert int(jnp.abs(q).max()) == 0
        assert float(jnp.abs(
            qcomm.dequantize_blockwise(q, s, 128)).max()) == 0.0

    def test_outlier_block_isolated(self):
        x = np.full(256, 0.01, np.float32)
        x[200] = 1000.0
        q, s = qcomm.quantize_blockwise(jnp.asarray(x), block=128)
        back = np.asarray(qcomm.dequantize_blockwise(q, s, 128))
        # the outlier-free block keeps its own tiny scale
        assert np.abs(back[:128] - 0.01).max() <= 0.01 / 254 + 1e-7

    def test_validation(self):
        with pytest.raises(ValueError):
            qcomm.quantized_all_reduce(jnp.ones(8), "dp", 0)
        with pytest.raises(ValueError):
            qcomm.quantized_all_reduce(jnp.ones(8), "dp", 2, block=0)


@needs_mesh
class TestQuantizedAllReduce:
    def test_matches_f32_psum_within_bound(self):
        mesh = create_mesh({"dp": 8})
        rng = np.random.RandomState(1)
        x = rng.randn(8, 1000).astype(np.float32) * 3.0

        f = shard_map(
            lambda xs: qcomm.quantized_all_reduce(
                xs[0], "dp", 8, block=128, mean=True),
            mesh=mesh, in_specs=(P("dp"),), out_specs=P(),
            check_vma=False)
        out = np.asarray(jax.jit(f)(x))
        ref = x.mean(0)
        # one quantization step per ring hop + one for the gather,
        # relative to the partial sums' amax — comfortably inside 4%
        # of the input amax in practice (measured ~0.4%)
        assert np.abs(out - ref).max() < 0.04 * np.abs(x).max()

    def test_axis_size_one_is_identity(self):
        mesh = create_mesh({"dp": 8})
        x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
        # n == 1 short-circuits (no collective traced)
        out = qcomm.quantized_all_reduce(jnp.asarray(x), "dp", 1)
        assert np.array_equal(np.asarray(out), x)

    def test_tree_shapes_and_dtypes(self):
        mesh = create_mesh({"dp": 8})
        rng = np.random.RandomState(2)
        tree = {"a": jnp.asarray(rng.randn(17, 5).astype(np.float32)),
                "b": jnp.asarray(rng.randn(33).astype(np.float32))
                .astype(jnp.bfloat16)}

        f = shard_map(
            lambda t: qcomm.quantized_all_reduce_tree(
                t, "dp", 8, block=64, mean=False),
            mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False)
        out = jax.jit(f)(tree)
        assert out["a"].shape == (17, 5) and out["a"].dtype == jnp.float32
        assert out["b"].shape == (33,) and out["b"].dtype == jnp.bfloat16
        ref = np.asarray(tree["a"]) * 8      # replicated inputs: sum = 8x
        assert np.abs(np.asarray(out["a"]) - ref).max() \
            < 0.1 * np.abs(ref).max() + 1e-3


@needs_mesh
class TestQuantizedDPTraining:
    def test_loss_curve_parity(self):
        toks = np.random.RandomState(0).randint(
            0, 64, (8, 16)).astype(np.int32)
        tr_f = _trainer("f32")
        lf = [float(tr_f.step(toks)) for _ in range(4)]
        tr_q = _trainer("int8")
        lq = [float(tr_q.step(toks)) for _ in range(4)]
        assert lf[0] == lq[0]        # step 1 uses pre-update params
        for a, b in zip(lf, lq):
            assert np.isfinite(b)
            assert abs(a - b) < 2e-2 * max(abs(a), 1.0), (lf, lq)
        assert lq[-1] < lq[0]        # still learning

    def test_collective_bytes_bound_and_dtype_gauges(self):
        from paddle_tpu.core import rng as rng_mod
        from paddle_tpu.profiler import instrument as pinstr
        from paddle_tpu.profiler import registry

        toks = np.random.RandomState(0).randint(
            0, 64, (8, 16)).astype(np.int32)

        def lowered_stats(tr):
            vs = tr._shard_batch((toks,))
            low = tr._step_fn.lower(
                tr.params, tr.opt_states, tr.buffers, vs,
                jnp.asarray(0.0, jnp.float32),
                jnp.asarray(0, jnp.int32), rng_mod.next_key())
            return pinstr.record_collectives_from(low, tr.mesh)

        st_q = lowered_stats(_trainer("int8"))
        # the per-dtype gauges read straight off the registry
        int8_b = registry().gauge("comm/collective_bytes_int8").value
        f32_b = registry().gauge("comm/collective_bytes_f32").value
        assert int8_b > 0
        assert st_q["bytes_by_dtype"].get("i8", 0) == int8_b
        # scale/loss traffic exists but the payload dominates
        assert f32_b < int8_b
        st_f = lowered_stats(_trainer("f32"))
        assert st_f["bytes_by_dtype"].get("i8", 0) == 0
        ratio = st_q["total_bytes"] / st_f["total_bytes"]
        # the ISSUE 12 acceptance bound: DP-gradient collective bytes
        # <= 0.55x the f32 baseline (measured ~0.46 at dp=8)
        assert ratio <= 0.55, ratio

    def test_data_spec_respected(self):
        # regression (review): a leaf the user explicitly REPLICATED
        # via data_spec must not be split across shards just because
        # its dim 0 divides dp — under the manual wrap each shard
        # would see a slice of a non-batch array and compute a wrong
        # local loss. With the spec honored, the qcomm loss equals the
        # GSPMD loss exactly at step 1 (pre-update params; the w-term
        # depends on seeing ALL of w).
        from jax.sharding import PartitionSpec as P

        rng = np.random.RandomState(0)
        x = rng.randn(16, 4).astype(np.float32)
        w = rng.randn(8).astype(np.float32)   # replicated, dim0 % 8 == 0

        def loss_fn(out, wt):
            return (out ** 2).mean() + (wt * wt).sum() * 0.01

        def make(dpc):
            paddle.seed(5)
            net = paddle.nn.Linear(4, 2)
            opt = paddle.optimizer.SGD(0.0, parameters=net.parameters())
            s = DistributedStrategy()
            return compile_train_step(
                net, opt, s, build_mesh_from_strategy(s),
                loss_fn=loss_fn, data_spec=(P("dp"), P()),
                dp_grad_comm=dpc)

        lf = float(make("f32").step(x, w))
        lq = float(make("int8").step(x, w))
        assert abs(lf - lq) < 1e-5, (lf, lq)

    def test_grad_merge_error_names_the_shard(self):
        # accumulate_steps divisibility under the wrap applies to the
        # PER-SHARD batch — the error must say so instead of naming a
        # batch size the user never passed
        tr = _trainer("int8", accumulate_steps=4)
        toks = np.zeros((16, 16), np.int32)     # global 16 % 4 == 0,
        with pytest.raises(ValueError, match="PER-SHARD"):
            tr.step(toks)                       # but shard 2 % 4 != 0

    def test_validation(self):
        with pytest.raises(ValueError, match="dp_grad_comm"):
            _trainer("int4")
        net = _micro_gpt()
        opt = paddle.optimizer.AdamW(2e-3, parameters=net.parameters())
        s = DistributedStrategy()
        s.hybrid_configs = {"mp_degree": 2}
        with pytest.raises(NotImplementedError, match="pure data"):
            compile_train_step(net, opt, s,
                               build_mesh_from_strategy(s),
                               dp_grad_comm="int8")
        # stages 1-2 now RUN the sharded update on the quantized ring
        # (test_zero_shard.py); stage 3 parameter sharding stays banned
        s2 = DistributedStrategy()
        s2.sharding = True
        s2.sharding_configs = {"sharding_stage": 3}
        with pytest.raises(NotImplementedError, match="ZeRO"):
            compile_train_step(net, opt, s2,
                               build_mesh_from_strategy(s2),
                               dp_grad_comm="int8")


@needs_mesh
@pytest.mark.slow
class TestHybridPipelineQcomm:
    def test_pipeline_trainer_parity_and_guard(self):
        from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
        from paddle_tpu.models import gpt_tiny

        toks = np.random.RandomState(0).randint(
            0, 128, (8, 32)).astype(np.int32)

        def make(dpc, **kw):
            paddle.seed(3)
            net = gpt_tiny()
            opt = paddle.optimizer.AdamW(2e-3,
                                         parameters=net.parameters())
            return HybridPipelineTrainer(net, opt, DistributedStrategy(),
                                         dp_grad_comm=dpc, **kw)

        lf = [float(make("f32").step(toks))]
        tr_q = make("int8")
        lq = [float(tr_q.step(toks))]
        assert abs(lf[0] - lq[0]) < 1e-6
        # guard_bad_steps composes: the verdict reads the REDUCED grads
        tr_g = make("int8", guard_bad_steps=True)
        tr_g.step(toks)
        assert tr_g.last_step_ok
        tr_g.inject_fault_scale(float("nan"))
        tr_g.step(toks)
        assert not tr_g.last_step_ok

    def test_pipeline_validation(self):
        from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
        from paddle_tpu.models import gpt_tiny

        paddle.seed(3)
        net = gpt_tiny()
        opt = paddle.optimizer.AdamW(2e-3, parameters=net.parameters())
        s = DistributedStrategy()
        s.hybrid_configs = {"pp_degree": 2}
        with pytest.raises(NotImplementedError, match="pure data"):
            HybridPipelineTrainer(net, opt, s, dp_grad_comm="int8")
