"""stream_layers (round 5): per-layer
host-stream ZeRO-Offload update in the hybrid trainer.

The TPU path stores host-offloaded state per-layer in pinned_host and
streams it through HBM behind a depth-bounded optimization_barrier
chain. XLA:CPU has no pinned_host memory space (jax 0.9), so these
tests set PADDLE_TPU_FAKE_PINNED_HOST=1: both "spaces" map to default
device memory — placement differs from hardware, but the program
structure (per-layer state lists, barrier chain, persistent bf16
compute copies, per-layer writeback) and all math are identical.
Hardware placement is exercised by bench.py's offload configs.

Reference analogue: the staged ZeRO-Offload update (reference:
python/paddle/incubate/optimizer/distributed_fused_lamb.py).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
from paddle_tpu.distributed.strategy_compiler import build_mesh_from_strategy


@pytest.fixture(autouse=True)
def _fake_pinned_host():
    os.environ["PADDLE_TPU_FAKE_PINNED_HOST"] = "1"
    yield
    os.environ.pop("PADDLE_TPU_FAKE_PINNED_HOST", None)


def _strategy(**kw):
    s = DistributedStrategy()
    s.hybrid_configs = kw.pop("hybrid", {})
    for k, v in kw.items():
        setattr(s, k, v)
    return s


def _make(seed=11, hybrid=None, n_micro=2, **kw):
    paddle.seed(seed)
    from paddle_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                    num_heads=4, max_seq_len=32)
    net = GPT(cfg)
    opt = paddle.optimizer.AdamW(5e-3, parameters=net.parameters())
    s = _strategy(amp=True, recompute=True, hybrid=hybrid or {},
                  pipeline=bool(hybrid))
    mesh = build_mesh_from_strategy(s)
    return HybridPipelineTrainer(net, opt, s, mesh, n_micro=n_micro, **kw)


def _toks(b=8, s=32, seed=0):
    return np.random.RandomState(seed).randint(0, 128, (b, s)) \
        .astype(np.int32)


class TestStreamLayersParity:
    def test_matches_whole_group_offload(self):
        """Same placement (masters + moments offloaded), two schedules:
        whole-group chain vs per-layer stream. The math is the same f32
        update on the same bf16-compute gradients, so losses agree."""
        toks = _toks()
        losses = {}
        for stream in (False, True):
            tr = _make(offload_params=True, offload_optimizer=True,
                       moment_dtype="bfloat16", stream_layers=stream)
            losses[stream] = [float(tr.step(toks)) for _ in range(6)]
        for a, b in zip(losses[False], losses[True]):
            assert abs(a - b) < 5e-3, (losses[False], losses[True])
        assert losses[True][-1] < losses[True][0]

    def test_resident_moments_matches_offloaded_moments(self):
        """The 1.3B bench config: masters offloaded per-layer, moments
        RESIDENT (halves host traffic). Placement must not change math."""
        toks = _toks()
        tr_a = _make(offload_params=True, offload_optimizer=True,
                     moment_dtype="bfloat16", stream_layers=True)
        tr_b = _make(offload_params=True, offload_optimizer=False,
                     moment_dtype="bfloat16", stream_layers=True)
        la = [float(tr_a.step(toks)) for _ in range(5)]
        lb = [float(tr_b.step(toks)) for _ in range(5)]
        for a, b in zip(la, lb):
            assert abs(a - b) < 5e-3, (la, lb)

    def test_comp_streamed_matches_comp_resident(self):
        """comp_resident=False (2.7B zero-argument layout): forward
        copies streamed per-layer from host masters in-program. Same
        math — bf16(master) either way — so losses agree exactly."""
        toks = _toks()
        tr_a = _make(offload_params=True, offload_optimizer=True,
                     moment_dtype="bfloat16", stream_layers=True)
        tr_b = _make(offload_params=True, offload_optimizer=True,
                     moment_dtype="bfloat16", stream_layers=True,
                     comp_resident=False)
        la = [float(tr_a.step(toks)) for _ in range(4)]
        lb = [float(tr_b.step(toks)) for _ in range(4)]
        for a, b in zip(la, lb):
            assert abs(a - b) < 5e-3, (la, lb)

    def test_conservative_fetch_matches_free_schedule(self):
        """conservative_fetch (the 1.9B fit knob) changes only the
        barrier gating — scheduling, not math."""
        toks = _toks()
        tr_a = _make(offload_params=True, offload_optimizer=True,
                     moment_dtype="bfloat16", stream_layers=True)
        tr_b = _make(offload_params=True, offload_optimizer=True,
                     moment_dtype="bfloat16", stream_layers=True,
                     conservative_fetch=True)
        la = [float(tr_a.step(toks)) for _ in range(3)]
        lb = [float(tr_b.step(toks)) for _ in range(3)]
        for a, b in zip(la, lb):
            assert abs(a - b) < 5e-3, (la, lb)

    def test_optimizer_only_stream_trains(self):
        """Case B: resident (bf16-stored) masters, per-layer host
        moments — the moments-offload scaling config."""
        tr = _make(offload_params=False, offload_optimizer=True,
                   param_dtype="bfloat16", moment_dtype="bfloat16",
                   stream_layers=True)
        toks = _toks()
        losses = [float(tr.step(toks)) for _ in range(6)]
        assert all(np.isfinite(v) for v in losses)
        assert losses[-1] < losses[0], losses

    def test_stream_under_pp2(self):
        """Per-layer pieces are [pp, ...]: every stage fetches its own
        layer-i slice; parity with the single-device stream."""
        toks = _toks()
        tr1 = _make(offload_params=True, offload_optimizer=True,
                    moment_dtype="bfloat16", stream_layers=True)
        l1 = [float(tr1.step(toks)) for _ in range(3)]
        tr2 = _make(hybrid={"pp_degree": 2},
                    offload_params=True, offload_optimizer=True,
                    moment_dtype="bfloat16", stream_layers=True)
        l2 = [float(tr2.step(toks)) for _ in range(3)]
        assert abs(l1[0] - l2[0]) < 2e-2, (l1, l2)
        assert all(np.isfinite(v) for v in l2)


class TestStreamLayersState:
    def test_sync_to_layer_restores_eager(self):
        tr = _make(offload_params=True, offload_optimizer=True,
                   moment_dtype="bfloat16", stream_layers=True,
                   free_eager=True)
        toks = _toks()
        losses = [float(tr.step(toks)) for _ in range(3)]
        assert losses[-1] < losses[0]
        model = tr.sync_to_layer()
        sd = model.state_dict()
        assert all(v is not None for v in sd.values())

    def test_device_state_roundtrip_resume_exact(self):
        toks = _toks()
        tr = _make(offload_params=True, offload_optimizer=True,
                   moment_dtype="bfloat16", stream_layers=True)
        for _ in range(3):
            tr.step(toks)
        # snapshot copies: device_state returns live references that the
        # next step's donation invalidates (checkpoint.save serializes
        # them to disk before any further step in the real flow)
        st = jax.tree_util.tree_map(jnp.copy, tr.device_state())
        expect = float(tr.step(toks))

        tr2 = _make(seed=99, offload_params=True, offload_optimizer=True,
                    moment_dtype="bfloat16", stream_layers=True)
        tr2.load_device_state(st, step=3)
        got = float(tr2.step(toks))
        assert abs(got - expect) < 1e-4, (got, expect)

    def test_memory_analysis_accounts_host_state(self):
        tr = _make(offload_params=True, offload_optimizer=True,
                   moment_dtype="bfloat16", stream_layers=True)
        ma = tr.memory_analysis(_toks())
        assert ma is None or "host_resident_argument_bytes" in ma
        if ma is not None:
            assert ma["host_resident_argument_bytes"] > 0


class TestStreamLayersValidation:
    def test_requires_offload(self):
        with pytest.raises(ValueError, match="stream_layers"):
            _make(stream_layers=True)

    def test_rejects_virtual_pipeline(self):
        with pytest.raises(ValueError, match="v_virtual"):
            _make(hybrid={"pp_degree": 2}, offload_params=True,
                  offload_optimizer=True, stream_layers=True,
                  v_virtual=2)
