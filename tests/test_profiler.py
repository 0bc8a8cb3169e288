"""paddle_tpu.profiler: tracing, metrics registry, recompilation
telemetry, and the trainer/bench instrumentation hooks.

Covers the observability contract: scope nesting, disabled-mode zero
side effects, metrics aggregation at world_size=1, chrome-trace export
round-trip, the retrace counter firing (exactly once) on an induced
shape change, the fleet metric helpers on plain Python scalars/lists,
and — under the ``profile`` marker (the CI smoke job) — one instrumented
HybridPipelineTrainer step whose exported trace file must be valid JSON.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.profiler as profiler


@pytest.fixture(autouse=True)
def _clean_profiler():
    """Profiler state is process-global: every test starts and ends
    disabled and empty."""
    if profiler.is_enabled():
        profiler.disable()
    profiler.reset()
    yield
    if profiler.is_enabled():
        profiler.disable()
    profiler.reset()


def _tiny_trainer():
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh
    from paddle_tpu.models import GPT, GPTConfig

    paddle.seed(11)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32)
    net = GPT(cfg)
    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
    mesh = create_mesh({"dp": 1}, jax.devices()[:1])
    tr = HybridPipelineTrainer(net, opt, DistributedStrategy(), mesh,
                               n_micro=1)
    toks = np.random.RandomState(0).randint(0, 128, (4, 32)).astype(
        np.int32)
    return tr, toks


class TestScopes:
    def test_scope_nesting_composes_names(self):
        profiler.enable()
        with profiler.scope("step"):
            with profiler.scope("h2d"):
                pass
            with profiler.scope("h2d"):
                pass
        s = profiler.scope_summary()
        assert s["step"]["count"] == 1
        assert s["step/h2d"]["count"] == 2
        assert s["step"]["total_ms"] >= s["step/h2d"]["total_ms"]

    def test_record_event_begin_end(self):
        profiler.enable()
        ev = profiler.RecordEvent("manual")
        ev.begin()
        ev.end()
        assert profiler.scope_summary()["manual"]["count"] == 1

    def test_scope_inside_jit_is_metadata_only(self):
        # a scope entered while tracing must not record a host span
        # (host-timing a tracer would measure tracing, not execution)
        profiler.enable()

        @jax.jit
        def f(x):
            with profiler.scope("traced/block"):
                return x * 2

        np.testing.assert_allclose(np.asarray(f(jnp.ones((2,)))), 2.0)
        assert "traced/block" not in profiler.scope_summary()

    def test_disabled_mode_zero_side_effects(self):
        assert not profiler.is_enabled()
        with profiler.scope("never"):
            with profiler.scope("nested"):
                pass
        assert profiler.trace.events() == []
        # retrace telemetry: signature history may accumulate, but the
        # public counter/log must not move while disabled
        f = jax.jit(profiler.watch(lambda x: x + 1, "t.disabled"))
        f(jnp.ones((2,)))
        f(jnp.ones((3,)))
        assert profiler.retraces() == []
        assert "profiler/retraces" not in profiler.registry().names()
        assert profiler.scope_summary() == {}


class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = profiler.registry()
        reg.counter("t/c").add(2)
        reg.counter("t/c").add(3)
        reg.gauge("t/g").set(7.0)
        reg.gauge("t/hw").set_max(5)
        reg.gauge("t/hw").set_max(3)          # high-water keeps the max
        for v in (1.0, 2.0, 3.0, 4.0):
            reg.histogram("t/h").observe(v)
        snap = reg.snapshot()
        assert snap["t/c"]["value"] == 5.0
        assert snap["t/g"]["value"] == 7.0
        assert snap["t/hw"]["value"] == 5.0
        assert snap["t/h"]["count"] == 4
        assert snap["t/h"]["mean"] == 2.5
        assert snap["t/h"]["min"] == 1.0 and snap["t/h"]["max"] == 4.0

    def test_type_collision_raises(self):
        reg = profiler.registry()
        reg.counter("t/x")
        with pytest.raises(TypeError):
            reg.gauge("t/x")

    def test_aggregate_world_size_1_is_identity(self):
        reg = profiler.registry()
        reg.counter("a/c").add(4)
        reg.gauge("a/g").set(2.5)
        reg.histogram("a/h").observe(1.0)
        assert reg.aggregate() == reg.snapshot()

    def test_aggregate_merges_rank_local_sketches(self, monkeypatch):
        """ISSUE 16 tentpole: aggregated histogram quantiles come from
        the bucket-wise MERGE of every rank's quantile sketch (exact —
        the mesh percentile equals a single union sketch's, within the
        sketch's rel_err), retiring the NaN-padded reservoir gather.
        The collectives are faked to simulate a 2-rank fleet: rank 1
        rides the same JSON-sketch wire with a disjoint value set —
        the quantiles must move to the union's."""
        import json

        import numpy as np

        from paddle_tpu.distributed import collective as coll
        from paddle_tpu.distributed import env as denv
        from paddle_tpu.distributed.fleet import metrics as fm
        from paddle_tpu.framework.tensor import Tensor
        from paddle_tpu.profiler.sketch import QuantileSketch

        reg = profiler.registry()
        h = reg.histogram("m/h")
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        peer_sk = QuantileSketch()
        for v in (5.0, 6.0, 7.0, 8.0):
            peer_sk.observe(v)
        peer_payload = np.frombuffer(
            json.dumps(peer_sk.to_dict()).encode(), np.uint8).copy()
        wire_sizes = {
            len(json.dumps(h.sketch_dict()).encode()),
            peer_payload.size,
        }

        monkeypatch.setattr(denv, "get_world_size", lambda: 2)
        monkeypatch.setattr(fm, "get_world_size", lambda: 2)
        monkeypatch.setattr(fm, "sum", lambda x, **kw: 2.0 * float(
            np.asarray(x, np.float64)))

        def fake_max(x, **kw):
            # the sketch-wire width allreduce must see BOTH ranks'
            # payload sizes; every other max is identity (same-schema
            # ranks, peer envelope not exercised here)
            v = float(np.asarray(x, np.float64))
            if v in wire_sizes:
                return float(max(wire_sizes))
            return v

        monkeypatch.setattr(fm, "max", fake_max)
        monkeypatch.setattr(fm, "min", lambda x, **kw: float(
            np.asarray(x, np.float64)))

        def fake_all_gather(out, tensor, group=None, **kw):
            local = np.asarray(tensor._value)
            out.append(Tensor(local))
            raw = bytes(local.astype(np.uint8)).rstrip(b"\x00")
            if isinstance(json.loads(raw.decode()), dict):  # sketch
                buf = np.zeros(local.shape, np.uint8)
                buf[: peer_payload.size] = peer_payload
                out.append(Tensor(buf))
            else:                               # schema-union gather
                out.append(Tensor(local))

        monkeypatch.setattr(coll, "all_gather", fake_all_gather)
        agg = reg.aggregate()["m/h"]
        assert agg["count"] == 8                # sum-reduced
        # nearest-rank percentiles over the UNION [1..8], within the
        # sketch's stated relative-error bound
        rel = QuantileSketch().rel_err
        assert abs(agg["p50"] - 5.0) <= rel * 5.0 + 1e-9
        assert abs(agg["p90"] - 8.0) <= rel * 8.0 + 1e-9
        assert abs(agg["p99"] - 8.0) <= rel * 8.0 + 1e-9
        assert agg["p50"] <= agg["p90"] <= agg["p99"]

    def test_schema_union_is_sorted_name_type_pairs(self):
        # the deterministic reduction order every rank walks in
        # aggregate() — identity (local schema) at world_size 1
        reg = profiler.registry()
        reg.gauge("b/y").set(1.0)
        reg.counter("a/x").add(2)
        union = profiler.MetricsRegistry._schema_union(reg.snapshot())
        assert union == [("a/x", "counter"), ("b/y", "gauge")]


class TestChromeTrace:
    def test_export_round_trip(self, tmp_path):
        profiler.enable()
        with profiler.scope("outer"):
            with profiler.scope("inner"):
                pass
        path = str(tmp_path / "trace.json")
        assert profiler.export_chrome_trace(
            path, extra_metadata={"run": "test"}) == path
        with open(path) as f:
            doc = json.load(f)
        names = [e["name"] for e in doc["traceEvents"]]
        assert sorted(names) == ["outer", "outer/inner"]
        for e in doc["traceEvents"]:
            assert e["ph"] == "X" and e["dur"] >= 0
        assert doc["otherData"] == {"run": "test"}
        # events survive the round trip with the same stats
        assert len(names) == sum(
            s["count"] for s in profiler.scope_summary().values())

    def test_event_cap_keeps_summary_exact(self, monkeypatch):
        from paddle_tpu.profiler import trace

        monkeypatch.setattr(trace, "_MAX_EVENTS", 5)
        profiler.enable()
        for _ in range(12):
            with profiler.scope("s"):
                pass
        assert len(trace.events()) == 5        # bounded span store
        assert profiler.scope_summary()["s"]["count"] == 12  # exact
        assert profiler.chrome_trace()["otherData"][
            "dropped_events"] == 7


class TestRecompileTelemetry:
    def test_retrace_counter_fires_on_shape_change(self):
        profiler.enable()
        f = jax.jit(profiler.watch(lambda x: x * 2, "t.shape"))
        f(jnp.ones((4, 8)))                    # first trace: not a retrace
        assert profiler.retraces() == []
        f(jnp.ones((4, 8)))                    # cache hit: nothing
        f(jnp.ones((4, 16)))                   # induced shape change
        assert profiler.registry().counter(
            "profiler/retraces").value == 1.0
        (ev,) = profiler.retraces()
        assert ev["site"] == "t.shape"
        assert ev["changed"][0]["prev"] == ((4, 8), "float32")
        assert ev["changed"][0]["new"] == ((4, 16), "float32")

    def test_trace_counts_tracked_even_when_disabled(self):
        f = jax.jit(profiler.watch(lambda x: x + 0.0, "t.counts"))
        f(jnp.ones((2,)))
        f(jnp.ones((5,)))
        assert profiler.trace_counts()["t.counts"] == 2
        assert profiler.retraces() == []       # disabled: log untouched

    def test_suppressed_lowering_not_counted(self):
        profiler.enable()
        f = jax.jit(profiler.watch(lambda x: x * 3, "t.suppress"))
        f(jnp.ones((2, 2)))
        with profiler.suppressed():
            f.lower(jnp.ones((8, 8)))          # diagnostic re-trace
        assert profiler.retraces() == []


class TestCollectiveStats:
    def test_counts_bytes_from_lowered_text(self):
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",))

        @jax.jit
        def f(x):
            return jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec())).sum()

        # hand-written StableHLO line: the parser is a text scan, so the
        # contract is testable without relying on what XLA emits on CPU
        text = ('%1 = "stablehlo.all_reduce"(%0) : '
                "(tensor<4x8xf32>) -> tensor<4x8xf32>")
        st = profiler.collective_stats(text)
        assert st["ops"] == {"all_reduce": 1}
        assert st["total_bytes"] == 4 * 8 * 4
        st2 = profiler.record_collective_stats(text)
        assert st2 == st
        snap = profiler.registry().snapshot()
        assert snap["comm/collective_bytes_per_step"]["value"] == 128.0

    def test_region_bearing_all_reduce_reads_result_type(self):
        # all_reduce/reduce_scatter carry their reduction as a region:
        # the function type prints on the closing `}) : ... -> ...` line,
        # and the op line's only tensor type is the replica_groups
        # attribute — which must NOT be counted as the payload
        text = "\n".join([
            '    %3 = "stablehlo.all_reduce"(%2) <{replica_groups = '
            "dense<0> : tensor<1x1xi64>, use_global_device_ids}> ({",
            "    ^bb0(%arg1: tensor<f32>, %arg2: tensor<f32>):",
            "      %8 = stablehlo.add %arg1, %arg2 : tensor<f32>",
            "      stablehlo.return %8 : tensor<f32>",
            "    }) : (tensor<8x4xf32>) -> tensor<8x4xf32>",
        ])
        st = profiler.collective_stats(text)
        assert st["ops"] == {"all_reduce": 1}
        assert st["total_bytes"] == 8 * 4 * 4

    def test_compiled_hlo_spelling(self):
        # post-partitioning HLO (`compiled.as_text()`): dash-separated
        # op names, result type(s) between `=` and the op name
        text = "\n".join([
            "  %ar = f32[8,4]{1,0} all-reduce(f32[8,4]{1,0} %p0), "
            "replica_groups={{0,1}}, to_apply=%add",
            "  %ag = (f32[16]{0}, f32[2]{0}) all-gather(f32[8]{0} %p1, "
            "f32[1]{0} %p2), dimensions={0}",
            # async pair: -start's result tuple aliases operand+result
            # (would double-count); only the -done payload is counted
            "  %s = (f32[8,4]{1,0}, f32[8,4]{1,0}) all-reduce-start("
            "f32[8,4]{1,0} %p3), replica_groups={{0,1}}, to_apply=%add",
            "  %d = f32[8,4]{1,0} all-reduce-done((f32[8,4]{1,0}, "
            "f32[8,4]{1,0}) %s)",
        ])
        st = profiler.collective_stats(text)
        assert st["ops"] == {"all_reduce": 2, "all_gather": 1}
        assert st["bytes"]["all_reduce"] == 2 * (8 * 4 * 4)
        assert st["bytes"]["all_gather"] == (16 + 2) * 4

    def test_real_lowering_all_reduce_bytes(self):
        # the same check against what THIS jax actually prints
        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices")
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",))
        P = jax.sharding.PartitionSpec

        f = jax.jit(jax.shard_map(
            lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
            in_specs=P("dp"), out_specs=P()))
        text = f.lower(jnp.ones((8, 4), jnp.float32)).as_text()
        st = profiler.collective_stats(text)
        assert st["ops"].get("all_reduce", 0) >= 1
        # per-shard payload is (4,4) f32 = 64 bytes; whatever partitioner
        # details change, the count must reflect a real f32 payload, not
        # the 8-byte replica_groups i64 attribute
        assert st["bytes"]["all_reduce"] >= 64


class TestTokensInBatch:
    def test_token_grid_vs_sample_batches(self):
        f = profiler.tokens_in_batch
        assert f([np.zeros((8, 32), np.int32)]) == 8 * 32   # token grid
        assert f([np.zeros((8, 32), np.float32)]) == 8      # feature mat
        assert f([np.zeros((64, 3, 28, 28), np.float32)]) == 64  # images
        assert f([np.zeros((5,), np.float32)]) == 5
        assert f([object()]) == 0


class TestFleetMetrics:
    """distributed/fleet/metrics.py on plain Python scalars and lists —
    the acc/auc helpers exercised at world_size=1."""

    def test_sum_max_min_scalars(self):
        from paddle_tpu.distributed.fleet import metrics as fm

        assert fm.sum(3) == 3.0 and isinstance(fm.sum(3), float)
        assert fm.max(2.5) == 2.5
        assert fm.min(-1) == -1.0

    def test_sum_lists_and_tensors(self):
        from paddle_tpu.distributed.fleet import metrics as fm

        out = fm.sum([1, 2, 3])
        np.testing.assert_allclose(out, [1.0, 2.0, 3.0])
        t = paddle.to_tensor(np.array([4.0, 5.0], np.float32))
        np.testing.assert_allclose(fm.max(t), [4.0, 5.0])

    def test_acc(self):
        from paddle_tpu.distributed.fleet import metrics as fm

        assert fm.acc(7, 10) == pytest.approx(0.7)
        assert fm.acc(0, 0) == 0.0             # empty batch: no div-by-0

    def test_auc(self):
        from paddle_tpu.distributed.fleet import metrics as fm

        # perfectly separated histograms -> AUC 1; symmetric -> 0.5
        assert fm.auc([0, 0, 0, 4], [4, 0, 0, 0]) == pytest.approx(1.0)
        assert fm.auc([2, 2], [2, 2]) == pytest.approx(0.5)
        assert fm.auc([0, 0], [0, 0]) == 0.0   # no samples


class TestSummary:
    def test_summary_rates_and_phases(self):
        profiler.enable()
        reg = profiler.registry()
        reg.counter("train/tokens").add(1000)
        reg.gauge("phase/fwd_ms").set(1.25)
        s = profiler.summary()
        assert s["enabled_window_s"] > 0
        assert s["rates"]["tokens_per_sec"] > 0
        assert s["phases_ms"] == {"fwd_ms": 1.25}
        d = profiler.disable()                 # returns the summary too
        assert d["metrics"]["train/tokens"]["value"] == 1000.0


@pytest.mark.profile
class TestInstrumentedTrainer:
    """The CI smoke job: one instrumented HybridPipelineTrainer step
    under JAX_PLATFORMS=cpu; the exported trace must be valid JSON."""

    def test_step_records_and_trace_file_is_valid_json(self, tmp_path):
        tr, toks = _tiny_trainer()
        profiler.enable()
        loss = tr.step(toks)
        assert np.isfinite(float(np.asarray(loss)))
        s = profiler.summary()
        assert s["metrics"]["train/steps"]["value"] == 1.0
        assert s["metrics"]["train/tokens"]["value"] == float(toks.size)
        assert s["metrics"]["hybrid/step_ms"]["count"] == 1
        assert {"hybrid/h2d", "hybrid/step"} <= set(s["scopes"])
        path = str(tmp_path / "trace.json")
        profiler.export_chrome_trace(path)
        with open(path) as f:
            doc = json.load(f)                 # must parse
        assert {e["name"] for e in doc["traceEvents"]} >= {
            "hybrid/h2d", "hybrid/step"}

    def test_phase_decomposition_and_induced_retrace(self):
        tr, toks = _tiny_trainer()
        profiler.enable()
        tr.step(toks)
        phases = tr.profile_step_phases(toks, iters=1)
        for k in ("fwd_ms", "bwd_ms", "optim_ms", "comm_ms", "step_ms"):
            assert k in phases, phases
        s = profiler.summary()
        assert {"fwd_ms", "bwd_ms", "optim_ms", "comm_ms"} <= \
            set(s["phases_ms"])
        assert s["rates"]["tokens_per_sec"] > 0
        # compiled-program accounting rides the phases pass: the step
        # program lands in the inventory keyed by its dispatch site,
        # with a timed compile (cost analysis is backend-dependent)
        (site,) = [k for k in s["programs"] if k.startswith("hybrid.step")]
        assert s["programs"][site]["compile_ms"] > 0
        assert s["retraces"] == []             # nothing silent so far
        # induced shape change -> the step retraces EXACTLY once
        tr.step(toks[:, :16])
        s = profiler.summary()
        assert len(s["retraces"]) == 1
        assert s["metrics"]["profiler/retraces"]["value"] == 1.0
        (ev,) = s["retraces"]
        assert ev["changed"], "diff must name the changed batch aval"

    def test_disabled_trainer_step_records_nothing(self):
        tr, toks = _tiny_trainer()
        tr.step(toks)
        assert profiler.trace.events() == []
        assert "train/steps" not in profiler.registry().names()
