"""Test configuration: force an 8-device virtual CPU mesh so SPMD logic is
exercised without TPU hardware (SURVEY.md §4 implication (b): XLA's
--xla_force_host_platform_device_count replaces the reference's
"2 subprocesses on localhost" distributed-test trick)."""
import jax

# Set through jax.config before any backend starts, so the suite means the
# CPU whatever the environment says. Never also set the XLA
# host-device-count flag: jax rejects the combination at backend init.
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_platforms", "cpu")

# Golden-value tests compare against float64 numpy: use exact fp32 matmuls.
# (The perf path keeps the platform default — bf16 on the MXU.)
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


# Breadth-first ordering for time-capped runs: the tier-1 CI window is
# hard-capped (870 s) and the suite does not fit inside it, so the
# compile-heavy integration files (each test builds + jits one or more
# hybrid trainers: tens of seconds per test) run LAST. The cap then
# truncates the expensive tail instead of broad cheap coverage. A full
# (uncapped) run is unaffected — every test still runs, only the order
# changes; relative order within each group is preserved (stable sort).
_COMPILE_HEAVY_FILES = frozenset({
    "test_checkpoint.py",        # hybrid resume-exact: 3 trainers
    "test_hybrid_models.py",     # bert/ernie/gpt hybrid compositions
    "test_pipeline_schedules.py",  # GPipe + interleaved schedules
    "test_stream_layers.py",     # per-layer offload streaming programs
    "test_async_pipeline.py",    # elastic/runner async pipeline
    "test_serving.py",           # serving engines: tick + bucket prefills
    "test_spec_decode.py",       # spec engines: draft tick + verify tick
    "test_kv_quant.py",          # int8-KV engines: quantized tick pairs
    "test_qcomm.py",             # quantized-DP trainers: 2 step compiles
    "test_zero_shard.py",        # ZeRO sharded-update trainer pairs
    "test_disagg.py",            # disagg serving: prefill+decode engines
})


def pytest_collection_modifyitems(config, items):
    items.sort(key=lambda it: it.fspath.basename in _COMPILE_HEAVY_FILES)


@pytest.fixture
def attention_spelling(monkeypatch):
    """``take(path)``: every program traced from then on takes its paged
    attention by ``path`` (``"pallas"``: the kernels, interpreted on the CPU;
    ``"xla"``) where it names none, whatever the platform. A tick names none:
    the spelling is picked at one seam, ``ops/paged_attention.resolve_impl``
    and ``ops/latent_attention.latent_attention_path``, and that is where a
    test that drives a whole engine through the other spelling substitutes."""
    from paddle_tpu.ops import latent_attention as la
    from paddle_tpu.ops import paged_attention as pa

    def take(path: str) -> None:
        monkeypatch.setattr(pa, "resolve_impl",
                            lambda impl=None: impl or path)
        monkeypatch.setattr(la, "latent_attention_path",
                            lambda q, pool, c_width, impl=None: impl or path)

    return take


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(102)
    np.random.seed(102)
    yield
