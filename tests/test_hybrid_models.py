"""Model-agnostic hybrid trainer (distributed/hybrid.py): BERT through
dp×tp×pp and an ERNIE-style config through ZeRO-3 + recompute.

Reference analogue: the fleet meta-optimizer chain is model-agnostic by
program rewriting (meta_optimizers/pipeline_optimizer.py:136 splits ANY
program by op_device); here model-agnosticism is the pipeline protocol.
"""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
from paddle_tpu.distributed.strategy_compiler import build_mesh_from_strategy
from paddle_tpu.models import bert_tiny, ernie_tiny


def _strategy(**kw):
    s = DistributedStrategy()
    s.hybrid_configs = kw.pop("hybrid", {})
    for k, v in kw.items():
        setattr(s, k, v)
    return s


def _bert_batch(vocab=128, b=8, s=32, seed=0):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, vocab, (b, s)).astype(np.int32)
    tt = rng.randint(0, 2, (b, s)).astype(np.int32)
    mlm = np.where(rng.rand(b, s) < 0.15,
                   rng.randint(0, vocab, (b, s)), -100).astype(np.int32)
    nsp = rng.randint(0, 2, (b,)).astype(np.int32)
    return tokens, tt, mlm, nsp


class TestBertHybrid:
    def test_bert_hybrid_matches_eager_loss_at_step0(self):
        paddle.seed(5)
        net = bert_tiny()
        net.eval()
        batch = _bert_batch(seed=3)
        eager = float(net.loss(*[paddle.to_tensor(a) for a in batch])
                      .numpy())
        net.train()
        opt = paddle.optimizer.SGD(0.0, parameters=net.parameters())
        s = _strategy(hybrid={"mp_degree": 2, "pp_degree": 2})
        mesh = build_mesh_from_strategy(s)
        tr = HybridPipelineTrainer(net, opt, s, mesh, n_micro=2)
        spmd = float(tr.step(*batch))
        assert abs(spmd - eager) < 2e-2, (spmd, eager)

    def test_bert_stages_share_the_head_with_uneven_masks(self):
        """pp2 x tp2, two micro-batches: each stage runs the head on one of
        them. Nearly every masked position lies in the first, so the loss
        is the whole batch's two means (MLM over masked positions, NSP
        over rows) only if a share's MLM mean is weighed by its count
        (``pipeline_head_terms``)."""
        import pytest
        from paddle_tpu.profiler import metrics

        paddle.seed(9)
        net = bert_tiny()
        net.eval()
        tokens, tt, mlm, nsp = _bert_batch(seed=7)
        mlm[4:, 1:] = -100                   # the second share keeps 4
        mlm[4:, 0] = tokens[4:, 0]
        batch = (tokens, tt, mlm, nsp)
        eager = float(net.loss(*[paddle.to_tensor(a) for a in batch])
                      .numpy())
        halves = [float(net.loss(*[paddle.to_tensor(a[h]) for a in batch])
                        .numpy()) for h in (slice(0, 4), slice(4, 8))]
        assert abs(sum(halves) / 2 - eager) > 1e-2   # the test can tell
        net.train()
        opt = paddle.optimizer.SGD(0.0, parameters=net.parameters())
        s = _strategy(hybrid={"mp_degree": 2, "pp_degree": 2})
        counter = metrics.registry().counter(
            "head/pp_share_traces{stages=2}")
        before = counter.value
        tr = HybridPipelineTrainer(net, opt, s, build_mesh_from_strategy(s),
                                   n_micro=2)
        assert float(tr.step(*batch)) == pytest.approx(eager, rel=2e-5)
        assert counter.value > before

    def test_bert_hybrid_training_decreases_loss(self):
        paddle.seed(6)
        net = bert_tiny()
        opt = paddle.optimizer.AdamW(2e-3, parameters=net.parameters())
        s = _strategy(hybrid={"dp_degree": 2, "mp_degree": 2,
                              "pp_degree": 2}, amp=True)
        mesh = build_mesh_from_strategy(s)
        tr = HybridPipelineTrainer(net, opt, s, mesh, n_micro=2)
        batch = _bert_batch(seed=4)
        losses = [float(tr.step(*batch)) for _ in range(5)]
        assert losses[-1] < losses[0]


class TestErnieZero3:
    def test_ernie_zero3_recompute_matches_eager_loss_at_step0(self):
        paddle.seed(7)
        net = ernie_tiny()
        net.eval()
        batch = _bert_batch(seed=5)
        eager = float(net.loss(*[paddle.to_tensor(a) for a in batch])
                      .numpy())
        net.train()
        opt = paddle.optimizer.SGD(0.0, parameters=net.parameters())
        s = _strategy(hybrid={"dp_degree": 4, "mp_degree": 2},
                      sharding=True, recompute=True)
        s.sharding_configs = {"sharding_stage": 3}
        mesh = build_mesh_from_strategy(s)
        tr = HybridPipelineTrainer(net, opt, s, mesh)
        spmd = float(tr.step(*batch))
        assert abs(spmd - eager) < 2e-2, (spmd, eager)

    def test_ernie_zero3_recompute_trains(self):
        paddle.seed(8)
        net = ernie_tiny()
        opt = paddle.optimizer.AdamW(
            2e-3, parameters=net.parameters(),
            grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
        s = _strategy(hybrid={"dp_degree": 4, "mp_degree": 2},
                      sharding=True, recompute=True, amp=True)
        s.sharding_configs = {"sharding_stage": 3}
        mesh = build_mesh_from_strategy(s)
        tr = HybridPipelineTrainer(net, opt, s, mesh)
        batch = _bert_batch(seed=6)
        losses = [float(tr.step(*batch)) for _ in range(5)]
        assert losses[-1] < losses[0]
        # ZeRO-3: params carry the dp axis
        used = set()
        for e in tr.block_specs[tr.block_suffixes[0]]:
            if e is not None:
                used.update(e if isinstance(e, tuple) else (e,))
        assert "dp" in used


class TestHeadInsideTP:
    """Scalar-loss pipeline egress under tp>1 (round-3 fix): the loss head
    runs INSIDE the manual-pp region with its vocab-sharded tp collectives
    riding GSPMD-auto; only a scalar crosses 'pp'. Previously disabled for
    tp>1 (full [n_micro, mb, seq, hidden] psum across pp, the north-star
    tp x pp configuration)."""

    def test_gpt_tp_pp_dp_head_inside_matches_legacy_egress(self):
        import os

        from paddle_tpu.models import gpt_tiny

        losses = {}
        for mode in ("1", "0"):
            os.environ["PADDLE_TPU_HEAD_INSIDE"] = mode
            try:
                paddle.seed(3)
                net = gpt_tiny()
                opt = paddle.optimizer.SGD(0.0, parameters=net.parameters())
                s = _strategy(hybrid={"dp_degree": 2, "mp_degree": 2,
                                      "pp_degree": 2}, pipeline=True)
                s.pipeline_configs = {"accumulate_steps": 2}
                mesh = build_mesh_from_strategy(s)
                tr = HybridPipelineTrainer(net, opt, s, mesh)
                toks = np.random.RandomState(1).randint(
                    0, 128, (8, 32)).astype(np.int32)
                losses[mode] = float(tr.step(toks))
            finally:
                os.environ.pop("PADDLE_TPU_HEAD_INSIDE", None)
        assert np.isfinite(losses["1"])
        # identical math, different egress: losses agree tightly
        assert abs(losses["1"] - losses["0"]) < 1e-4, losses

    def test_gpt_tp_pp_head_inside_trains(self):
        from paddle_tpu.models import gpt_tiny

        paddle.seed(4)
        net = gpt_tiny()
        opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
        s = _strategy(hybrid={"mp_degree": 2, "pp_degree": 2},
                      pipeline=True)
        s.pipeline_configs = {"accumulate_steps": 2}
        mesh = build_mesh_from_strategy(s)
        tr = HybridPipelineTrainer(net, opt, s, mesh)
        toks = np.random.RandomState(2).randint(
            0, 128, (8, 32)).astype(np.int32)
        losses = [float(tr.step(toks)) for _ in range(4)]
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]


class TestMemoryKnobs:
    """Round-3 billion-param knobs (hybrid.py): reduced-precision state,
    layer-scan schedule, eager-buffer freeing. The pinned_host offload
    knobs need a TPU memory space and are exercised by bench.py on
    hardware (XLA:CPU has no pinned_host, jax 0.9)."""

    def _train(self, **kw):
        paddle.seed(11)
        from paddle_tpu.models import GPT, GPTConfig

        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                        num_heads=4, max_seq_len=32)
        net = GPT(cfg)
        opt = paddle.optimizer.AdamW(5e-3, parameters=net.parameters())
        s = _strategy(amp=False, recompute=True)
        mesh = build_mesh_from_strategy(s)
        tr = HybridPipelineTrainer(net, opt, s, mesh, n_micro=2, **kw)
        toks = np.random.RandomState(0).randint(
            0, 128, (8, 32)).astype(np.int32)
        losses = [float(tr.step(toks)) for _ in range(8)]
        return tr, losses

    def test_bf16_state_trains_and_sync_restores(self):
        tr, losses = self._train(param_dtype="bfloat16",
                                 moment_dtype="bfloat16",
                                 unroll_layers=False)
        assert losses[-1] < losses[0], losses
        model = tr.sync_to_layer()
        for _, t in model.named_parameters():
            assert t._value is not None

    def test_free_eager_without_dtype_cast(self):
        """r3 regression: device_put with unchanged dtype+sharding can
        ALIAS the eager buffer — free_eager must not delete buffers the
        trainer itself references."""
        tr, losses = self._train(free_eager=True)
        assert losses[-1] < losses[0], losses
        assert all(np.isfinite(v) for v in losses)

    def test_free_eager_releases_then_sync_restores(self):
        tr, losses = self._train(param_dtype="bfloat16", free_eager=True)
        assert losses[-1] < losses[0], losses
        # eager buffers were dropped during training...
        # ...and sync_to_layer rebuilds them for checkpointing
        model = tr.sync_to_layer()
        sd = model.state_dict()
        assert all(v is not None for v in sd.values())

    def test_bf16_state_matches_f32_early_steps(self):
        """bf16 master+moments stays within loss-noise of f32 for the
        first steps (per-step drift bounded; long-horizon parity is the
        125M loss-curve artifact, LOSSCURVE_r03.json)."""
        _, l32 = self._train()
        _, l16 = self._train(param_dtype="bfloat16",
                             moment_dtype="bfloat16")
        assert abs(l16[0] - l32[0]) < 1e-2, (l16[0], l32[0])
        assert abs(l16[-1] - l32[-1]) < 0.15, (l16[-1], l32[-1])

    def test_offload_params_requires_amp(self):
        import pytest

        with pytest.raises(ValueError, match="amp"):
            self._train(offload_params=True)


class TestMaskedPositionMLMHead:
    """config.max_predictions gathers masked positions before the vocab
    projection (reference: create_pretraining_data masked_lm_positions).
    With a generous budget the objective is EXACTLY the full-sequence
    ignore-index CE."""

    def test_gathered_head_matches_full_head(self):
        paddle.seed(7)
        net = bert_tiny()                       # full-sequence head
        opt = paddle.optimizer.SGD(0.0, parameters=net.parameters())
        s = _strategy()
        mesh = build_mesh_from_strategy(s)
        tr = HybridPipelineTrainer(net, opt, s, mesh)
        batch = _bert_batch(seed=11)
        full = float(tr.step(*batch))

        paddle.seed(7)                          # same init
        # 16 < s=32 so the gather branch EXECUTES; the ~15% mask rate
        # puts ~5 masked positions per row, far under 16, so no masked
        # position is dropped and the objective is identical
        net2 = bert_tiny(max_predictions=16)
        assert (np.sum(batch[2] != -100, axis=1) <= 16).all()
        opt2 = paddle.optimizer.SGD(0.0, parameters=net2.parameters())
        tr2 = HybridPipelineTrainer(net2, opt2, s, mesh)
        gathered = float(tr2.step(*batch))
        assert abs(full - gathered) < 1e-4, (full, gathered)

    def test_gathered_head_trains(self):
        paddle.seed(8)
        net = bert_tiny(max_predictions=8)
        opt = paddle.optimizer.AdamW(2e-3, parameters=net.parameters())
        s = _strategy(amp=True)
        mesh = build_mesh_from_strategy(s)
        tr = HybridPipelineTrainer(net, opt, s, mesh)
        batch = _bert_batch(seed=9)
        losses = [float(tr.step(*batch)) for _ in range(5)]
        assert losses[-1] < losses[0]
