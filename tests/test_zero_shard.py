"""ZeRO-1/2 sharded weight update (ISSUE 19): standalone ring
reduce-scatter / all-gather units, flat-update slice invariance (the
bitwise-parity mechanism), trainer-level loss parity of the sharded
update vs the replicated GSPMD path, the memory ledger's 1/dp
opt-state claim, sharded checkpoint save/restore/walk-back, the
mesh-agreed rollback-target reducer (state-lockstep satellite), and
the validation errors. Heavy compiles ride ONE combined tier-1 test
per trainer pair (conftest orders this file with the compile-heavy
tail); the quantized and guarded-hybrid legs are slow-marked."""
import os

import functools

import numpy as np
import pytest

import jax

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.distributed import qcomm  # noqa: E402
from paddle_tpu.distributed.elastic import ElasticTrainer  # noqa: E402
from paddle_tpu.distributed.fleet import DistributedStrategy  # noqa: E402
from paddle_tpu.distributed.mesh import create_mesh  # noqa: E402
from paddle_tpu.distributed.strategy_compiler import (  # noqa: E402
    build_mesh_from_strategy, compile_train_step)
from paddle_tpu.models import GPT, GPTConfig  # noqa: E402
from paddle_tpu.resilience.runner import _resilience_reducer  # noqa: E402

shard_map = functools.partial(jax.shard_map, check_vma=False)

N_DEV = len(jax.devices())
needs_mesh = pytest.mark.skipif(N_DEV < 8,
                                reason="needs the 8-device CPU mesh")

IDS = np.random.RandomState(0).randint(0, 64, (8, 32)).astype(np.int32)
LBL = np.roll(IDS, -1, axis=1).astype(np.int32)


def _micro_gpt():
    paddle.seed(3)
    return GPT(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=2, max_seq_len=32))


def _trainer(zero=0, dpc="f32", ppc=None, **kw):
    net = _micro_gpt()
    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters(),
                                 weight_decay=0.01)
    s = DistributedStrategy()
    if zero:
        s.sharding = True
        s.sharding_configs = {"sharding_stage": zero}
    mesh = build_mesh_from_strategy(s)
    return compile_train_step(net, opt, s, mesh, dp_grad_comm=dpc,
                              dp_param_comm=ppc, **kw)


class TestZeroChunkLen:
    def test_exact_multiple(self):
        # 8 ranks x 2 blocks of 4: no padding needed
        assert qcomm.zero_chunk_len(64, 8, 4) == 8

    def test_rounds_up_to_block(self):
        c = qcomm.zero_chunk_len(65, 8, 4)
        assert c == 12 and c % 4 == 0 and 8 * c >= 65

    def test_minimum_one_block(self):
        assert qcomm.zero_chunk_len(1, 8, 2048) == 2048


@needs_mesh
class TestRingCollectiveUnits:
    def _mesh(self):
        return create_mesh({"dp": 8}, jax.devices()[:8])

    def test_f32_reduce_scatter_matches_psum_slice(self):
        mesh = self._mesh()
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(8, 64).astype(np.float32))

        def body(xs):
            x_ = xs.reshape(-1)
            c = qcomm.reduce_scatter(x_, "dp", 8)
            return c[None]

        out = shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                        out_specs=P("dp"))(x)
        want = np.asarray(x).sum(0).reshape(8, 8)
        got = np.asarray(out)
        # device r owns chunk r; sequential ring sum within f32 tolerance
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)

    def test_quantized_rs_then_ag_equals_quantized_all_reduce(self):
        mesh = self._mesh()
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(8, 4096).astype(np.float32))

        def fused(xs):
            return qcomm.quantized_all_reduce(xs.reshape(-1), "dp", 8,
                                              block=512, mean=True)[None]

        def split(xs):
            c = qcomm.quantized_reduce_scatter(xs.reshape(-1), "dp", 8,
                                               block=512, mean=True)
            return qcomm.quantized_all_gather(c, "dp", block=512)[None]

        # (each one program: dispatched operation by operation over eight
        # devices the two rings took over a minute)
        a = jax.jit(shard_map(fused, mesh=mesh, in_specs=(P("dp"),),
                              out_specs=P("dp")))(x)
        b = jax.jit(shard_map(split, mesh=mesh, in_specs=(P("dp"),),
                              out_specs=P("dp")))(x)
        # the fused spelling IS the composition now — bitwise
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_all_gather_cast_bf16_roundtrip(self):
        mesh = self._mesh()
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)

        def body(xs):
            full = qcomm.all_gather_cast(xs.reshape(-1), "dp",
                                         dtype=jnp.bfloat16)
            return full[None]

        out = shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                        out_specs=P("dp"))(x)
        # small integers are exact in bf16; row order must equal chunk
        # order (no roll)
        np.testing.assert_array_equal(
            np.asarray(out)[0], np.arange(64, dtype=np.float32))


class TestFlatUpdateSliceInvariance:
    def test_full_slab_equals_concatenated_slices(self):
        """The mechanism behind bitwise parity: AdamW on the flat fused
        buffer is elementwise, so updating the whole slab equals
        updating each shard's slice independently — bit for bit."""
        from paddle_tpu.distributed.strategy_compiler import (
            _FlatShim, make_flat_update)

        net = _micro_gpt()
        opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters(),
                                     weight_decay=0.01)
        upd = make_flat_update(opt)
        rng = np.random.RandomState(3)
        p = jnp.asarray(rng.randn(256).astype(np.float32))
        g = jnp.asarray(rng.randn(256).astype(np.float32))
        st = opt._init_state(_FlatShim(p))
        lr = jnp.float32(1e-3)
        sn = jnp.int32(1)
        one = jnp.float32(1.0)
        wd = jnp.float32(0.01)
        pf, sf = upd(p, g, st, lr, sn, one, wd)
        halves = [upd(p[i:i + 128], g[i:i + 128],
                      {k: v[i:i + 128] for k, v in st.items()},
                      lr, sn, one, wd) for i in (0, 128)]
        np.testing.assert_array_equal(
            np.asarray(pf),
            np.concatenate([np.asarray(h[0]) for h in halves]))
        for k in sf:
            np.testing.assert_array_equal(
                np.asarray(sf[k]),
                np.concatenate([np.asarray(h[1][k]) for h in halves]))


class TestValidationErrors:
    _MESH8 = type("M", (), {"shape": {"dp": 8}})()

    def test_int8_zero3_still_banned(self):
        with pytest.raises(NotImplementedError, match="ZeRO"):
            qcomm.validate_dp_grad_comm("int8", self._MESH8,
                                        zero_stage=3)

    def test_int8_zero12_allowed(self):
        qcomm.validate_dp_grad_comm("int8", self._MESH8, zero_stage=1)
        qcomm.validate_dp_grad_comm("int8", self._MESH8, zero_stage=2)

    def test_param_comm_value(self):
        with pytest.raises(ValueError, match="dp_param_comm"):
            qcomm.validate_dp_param_comm("f16", True)

    def test_param_comm_needs_sharded_update(self):
        with pytest.raises(ValueError, match="sharded"):
            qcomm.validate_dp_param_comm("int8", False)

    @needs_mesh
    def test_per_leaf_clip_rejected(self):
        from paddle_tpu.nn import ClipGradByValue

        net = _micro_gpt()
        opt = paddle.optimizer.AdamW(
            1e-3, parameters=net.parameters(),
            grad_clip=ClipGradByValue(1.0))
        s = DistributedStrategy()
        s.sharding = True
        s.sharding_configs = {"sharding_stage": 1}
        with pytest.raises(NotImplementedError, match="global norm"):
            compile_train_step(net, opt, s, build_mesh_from_strategy(s))


@needs_mesh
class TestZeroShardedTrainer:
    def test_f32_bitwise_parity_ledger_ckpt_lockstep(self, tmp_path):
        """ONE combined heavy leg (two trainer compiles): f32 sharded
        update vs replicated GSPMD — bitwise LOSSES over 3 steps
        (params differ only by reduction-order ulps: the sharded path
        sums per-shard local-mean grads on the ring where GSPMD psums
        globally-scaled partials; the update itself is slice-invariant,
        TestFlatUpdateSliceInvariance); the memory ledger's <= 1/dp +
        5% opt-state claim; the per-kind collective gauges;
        single-trace discipline; sharded save -> restore -> bitwise
        resume; the degraded walk-back; and the capped (mesh-target)
        restore the lockstep satellite added."""
        from paddle_tpu.profiler import recompile as _precomp
        from paddle_tpu.profiler.metrics import registry as _reg

        ref = _trainer(0)
        # block=512 keeps chunk padding negligible on the micro model
        # (block=2048 pads a 28k-param model past the 1/dp+5% bound)
        tz = _trainer(1, dp_grad_block=512)
        assert tz.zero_manual and not ref.zero_manual
        for _ in range(3):
            lf = float(np.asarray(ref.step(IDS, LBL)))
            lz = float(np.asarray(tz.step(IDS, LBL)))
            assert lf == lz, "sharded f32 loss diverged from replicated"
        for a, b in zip(ref.params, tz.params):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=1e-5)

        # -- memory ledger: opt state at 1/dp (+5% padding slack) ------
        led_ref = ref.memory_ledger()
        led_z = tz.memory_ledger()
        assert led_z["param"] == led_ref["param"]
        ratio = led_z["opt_state"] / led_ref["opt_state"]
        assert ratio <= 1.0 / 8 + 0.05, ratio
        assert "master" not in led_z          # f32 gather needs none
        g = _reg().gauge("mem/opt_state_bytes")
        assert g.value == led_z["opt_state"]

        # -- sharded-update program moves reduce-scatter + all-gather --
        from paddle_tpu.core import rng as rng_mod
        from paddle_tpu.profiler import instrument as _pinstr

        vs = tz._shard_batch((IDS, LBL))
        lowered = tz._step_fn.lower(
            tz.params, tz.opt_states, tz.buffers, vs,
            jnp.asarray(0.0, jnp.float32), jnp.asarray(0, jnp.int32),
            rng_mod.next_key())
        st = _pinstr.record_collectives_from(lowered, tz.mesh)
        bkd = st["bytes_by_kind_dtype"]
        assert _reg().gauge(
            "comm/collective_bytes_reduce_scatter_f32").value > 0, bkd
        assert _reg().gauge(
            "comm/collective_bytes_all_gather_f32").value > 0, bkd

        # -- single-trace discipline -----------------------------------
        assert _precomp.trace_counts().get(tz._prof_site, 0) == 1

        # -- sharded save -> restore -> bitwise resume -----------------
        el = ElasticTrainer(tz, str(tmp_path / "ck"), save_interval=100,
                            keep=10, verify_restore=True)
        el.save(3, async_=False)
        slab3 = {k: np.asarray(v) for k, v in tz.opt_states.items()}
        loss4 = float(np.asarray(tz.step(IDS, LBL)))
        assert el.resume() == 3
        assert tz.opt_states["moment1"].sharding.spec == P("dp")
        for k, v in tz.opt_states.items():
            np.testing.assert_array_equal(slab3[k], np.asarray(v))
        assert float(np.asarray(tz.step(IDS, LBL))) == loss4

        # -- degraded walk-back past a corrupt newest step -------------
        el.save(5, async_=False)
        step5 = tmp_path / "ck" / "step_00000005"
        shard = next(p for p in step5.iterdir()
                     if p.name.startswith("shard"))
        shard.write_bytes(b"garbage")
        with pytest.warns(RuntimeWarning, match="unreadable"):
            assert el.resume() == 3

        # -- capped restore: the mesh-agreed rollback target -----------
        el.save(8, async_=False)          # a commit PAST the target
        assert el.resume(max_step=3) == 3
        for k, v in tz.opt_states.items():
            np.testing.assert_array_equal(slab3[k], np.asarray(v))


@needs_mesh
@pytest.mark.slow
class TestZeroQuantized:
    def test_int8_parity_bytes_and_master(self, ):
        """Sharded int8 ring: step-1 loss within fp tolerance of the
        f32 replicated path, trajectory within the PR 12 quantization
        bound, dp_param_comm defaults to bf16 with an f32 master copy
        ledgered separately, and the RS+AG wire bytes do not exceed the
        fused quantized AllReduce's (int8 gather spelling)."""
        ref = _trainer(0)
        lf = [float(np.asarray(ref.step(IDS, LBL))) for _ in range(4)]
        tq = _trainer(2, "int8", dp_grad_block=512)
        assert tq.dp_param_comm == "bf16"
        lq = [float(np.asarray(tq.step(IDS, LBL))) for _ in range(4)]
        assert abs(lf[0] - lq[0]) < 1e-6      # step 1: same start state
        assert max(abs(a - b) for a, b in zip(lf, lq)) <= 5e-3
        led = tq.memory_ledger()
        assert led["master"] > 0
        # master is NOT part of the opt_state claim (it would break the
        # 1/dp bound); it is its own ledger line
        assert led["opt_state"] + led["master"] < 2 * led["param"]

        from paddle_tpu.core import rng as rng_mod
        from paddle_tpu.profiler import instrument as _pinstr

        def step_bytes(tr):
            vs = tr._shard_batch((IDS, LBL))
            lowered = tr._step_fn.lower(
                tr.params, tr.opt_states, tr.buffers, vs,
                jnp.asarray(0.0, jnp.float32),
                jnp.asarray(0, jnp.int32), rng_mod.next_key())
            return _pinstr.record_collectives_from(
                lowered, tr.mesh)["total_bytes"]

        fused = _trainer(0, "int8")           # PR 12 quantized AllReduce
        ti = _trainer(2, "int8", ppc="int8", dp_grad_block=512)
        assert step_bytes(ti) <= step_bytes(fused) * 1.01


@needs_mesh
@pytest.mark.slow
class TestGuardZeroHybrid:
    def test_guard_deselect_bitwise_on_sharded_path(self):
        """guard_bad_steps x ZeRO on the pipeline trainer's quantized
        ring: a NaN fault (which survives the int8 hops as NaN block
        scales) flips the mesh-agreed verdict and the deselect holds
        params AND the dp-sharded flat opt slab bit-identical."""
        from paddle_tpu.distributed.hybrid import (_ZERO_SLAB,
                                                   HybridPipelineTrainer)
        from paddle_tpu.models import gpt_tiny

        toks = np.random.RandomState(0).randint(
            0, 128, (8, 32)).astype(np.int32)
        paddle.seed(3)
        net = gpt_tiny()
        opt = paddle.optimizer.AdamW(2e-3, parameters=net.parameters())
        s = DistributedStrategy()
        s.sharding = True
        s.sharding_configs = {"sharding_stage": 1}
        tr = HybridPipelineTrainer(net, opt, s, dp_grad_comm="int8",
                                   guard_bad_steps=True)
        assert tr.zero_manual
        tr.step(toks)
        assert tr.last_step_ok
        p0 = [np.asarray(v) for v in jax.tree_util.tree_leaves(
            (tr.block_vals, tr.other_vals))]
        s0 = {k: np.asarray(v)
              for k, v in tr.block_opt[_ZERO_SLAB].items()}
        tr.inject_fault_scale(float("nan"))
        tr.step(toks)
        assert not tr.last_step_ok
        for a, b in zip(p0, jax.tree_util.tree_leaves(
                (tr.block_vals, tr.other_vals))):
            np.testing.assert_array_equal(a, np.asarray(b))
        for k, v in tr.block_opt[_ZERO_SLAB].items():
            np.testing.assert_array_equal(s0[k], np.asarray(v))
        tr.inject_fault_scale(1.0)
        tr.step(toks)
        assert tr.last_step_ok


class TestRollbackTargetReducer:
    def test_target_is_min_of_restorables(self):
        votes = {0: {"verdict": "rollback", "bad_cursors": [3, 4],
                     "restorable": 3},
                 1: {"verdict": "healthy", "bad_cursors": [],
                     "restorable": 6}}
        dec = _resilience_reducer(votes)
        assert dec["verdict"] == "rollback"
        assert dec["bad_cursors"] == [3, 4]
        # rank 1 committed at 6 AFTER rank 0's streak began: the mesh
        # target is rank 0's 3, or rank 1 resumes younger state and the
        # mesh leaves state-lockstep
        assert dec["target"] == 3

    def test_nothing_restorable(self):
        votes = {0: {"verdict": "rollback", "bad_cursors": [1],
                     "restorable": -1},
                 1: {"verdict": "healthy", "bad_cursors": [],
                     "restorable": -1}}
        assert _resilience_reducer(votes)["target"] == -1

    def test_votes_without_field_stay_decidable(self):
        # rounds joined by an older peer (no restorable in its vote)
        votes = {0: {"verdict": "rollback", "bad_cursors": [2],
                     "restorable": 4},
                 1: {"verdict": "healthy", "bad_cursors": []}}
        dec = _resilience_reducer(votes)
        assert dec["verdict"] == "rollback" and dec["target"] == 4
