"""CPU rehearsal of chip_smoke.py: the same phase functions the chip run
calls, at toy sizes on the virtual CPU mesh (Pallas kernels interpreted),
plus the script's refusals — no accelerator, no backend at import, and
where the compile cache goes."""
import dataclasses
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

import chip_smoke
from paddle_tpu.models import GPTConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.filterwarnings("ignore")

TOY = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=2,
                max_seq_len=128)


@pytest.fixture
def keep_cache_config():
    """main() and the helper place the compile cache through jax.config;
    the rest of the suite must not inherit that."""
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_main_refuses_a_cpu_backend(capsys, keep_cache_config):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out     # no result printed


def test_kernels_rehearsal():
    errs = chip_smoke.check_flash(1, 128, 2, 64, jnp.bfloat16)
    assert set(errs) == {"fwd", "dq", "dk", "dv"}
    # both pool dtypes and both row kinds; the four combinations are
    # compiled for the v5e in test_tpu_lowering.py
    chip_smoke.check_ragged(41, 4, 2, 64, 8, 8, False)
    chip_smoke.check_ragged(41, 4, 2, 64, 8, 1, True)


@pytest.mark.parametrize("forced", [False, True])
def test_experts_rehearsal(forced):
    r = chip_smoke.check_dropless(256, 64, 32, 8, 2, jnp.bfloat16, forced)
    assert (r["load"] >= 4.0) == forced     # 4: every token's first choice
    assert r["path"] == "xla"               # the CPU's; the chip's is pallas


def test_experts_check_catches_a_capacity(monkeypatch):
    """A drop-less layer that loses rows fails the phase: here the fullest
    expert's group is cut to twice the mean, as a capacity would."""
    import jax
    from paddle_tpu.distributed import moe

    real = jax.lax.ragged_dot

    def capped(lhs, rhs, group_sizes, **kw):
        cap = 2 * lhs.shape[0] // group_sizes.shape[0]
        rows = jnp.arange(lhs.shape[0])
        start = jnp.cumsum(group_sizes) - group_sizes
        group = jnp.searchsorted(jnp.cumsum(group_sizes), rows, side="right")
        keep = rows - start[group] < cap
        return jnp.where(keep[:, None], real(lhs, rhs, group_sizes, **kw), 0)

    monkeypatch.setattr(moe.jax.lax, "ragged_dot", capped)
    chip_smoke.check_dropless(256, 64, 32, 8, 2, jnp.bfloat16, False)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_dropless(256, 64, 32, 8, 2, jnp.bfloat16, True)


def test_kernel_check_catches_a_wrong_kernel(monkeypatch):
    from paddle_tpu.ops import paged_attention as pa

    real = pa._ragged_attention_pallas
    monkeypatch.setattr(pa, "_ragged_attention_pallas",
                        lambda *a, **k: real(*a, **k) * 1.1)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_ragged(41, 4, 2, 64, 8, 8, False)


@pytest.mark.parametrize("w_is_vh", [True, False])
def test_head_rehearsal(w_is_vh):
    errs = chip_smoke.check_head(2, 512, 32, 96, w_is_vh, jnp.bfloat16)
    assert set(errs) == {"loss", "dx", "dW"}


@pytest.mark.parametrize("at,name", [(0, "dx"), (1, "dW")])
def test_the_head_check_sees_a_fault_in_a_gradient(monkeypatch, at, name):
    """One of the saved gradients a tenth short: the check names it."""
    from paddle_tpu.ops import fused_ce

    real = fused_ce._fused_ce_bwd

    def planted(*a):
        out = list(real(*a))
        out[at] = (0.9 * out[at]).astype(out[at].dtype)
        return tuple(out)

    # the custom_vjp reads its ``bwd`` at each call
    monkeypatch.setattr(fused_ce._fused_ce, "bwd", planted)
    with pytest.raises(chip_smoke.SmokeFailure, match=f"head {name} error"):
        chip_smoke.check_head(2, 512, 32, 96, True, jnp.bfloat16)


SERVE_TOY = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                      num_heads=2, max_seq_len=256)
SERVE_REQUESTS = ((0, 6, 8, 0), (0, 40, 8, 16), (0, 120, 6, 0), (2, 9, 10, 0),
                  (30, 48, 8, 16), (34, 5, 6, 0))


def test_solar_rehearsal():
    """The small Solar-Open2's step against its reference; on the CPU the
    scan takes the ``jax.numpy`` path, and the phase says which it saw."""
    out = chip_smoke.phase_solar(128, "xla", (1, 64, 256))
    assert out["rel"] <= chip_smoke.TOL_SOLAR_LOSS
    assert all(out["scan"][n] <= tol
               for n, tol in chip_smoke.TOL_SOLAR_SCAN.items()), out["scan"]
    assert len(out["prep"]) == 9 and max(out["prep"].values()) == 0.0
    with pytest.raises(AssertionError, match="not the pallas path"):
        chip_smoke.phase_solar(128, "pallas", (1, 64, 256))


def test_the_prep_check_sees_a_fault_in_the_backward_pass(monkeypatch):
    """The chain's kernels interpreted where the phase compares them with
    the spelling, one tap's gradient a tenth short: the phase names it."""
    from paddle_tpu.ops import kda_prep

    real = kda_prep._pallas_bwd

    def planted(*a):
        dps, dws = real(*a)
        return dps, (dws[0], 0.9 * dws[1], dws[2])

    monkeypatch.setattr(kda_prep, "prep_path", lambda *a: "pallas")
    assert max(chip_smoke.prep_against_spelling(
        (1, 64, 256), "pallas").values()) <= chip_smoke.TOL_SOLAR_PREP
    monkeypatch.setattr(kda_prep, "_pallas_bwd", planted)
    with pytest.raises(AssertionError, match="the chain's dconv_k "):
        chip_smoke.prep_against_spelling((1, 64, 256), "pallas")


@pytest.mark.parametrize("at,name", [(1, "dk"), (3, "dg"), (4, "dbeta")])
def test_the_scan_check_sees_a_fault_in_the_backward_pass(monkeypatch, at,
                                                          name):
    """One of ``_chunk_bwd``'s gradients a tenth short: the phase's
    comparison with the recurrence names it."""
    from paddle_tpu.ops import kda

    real = kda._chunk_bwd

    def planted(*a, **k):
        out = list(real(*a, **k))
        out[at] = 0.9 * out[at]
        return tuple(out)

    monkeypatch.setattr(kda, "_chunk_bwd", planted)
    with pytest.raises(AssertionError, match=f"the scan's {name} "):
        chip_smoke.scan_against_recurrence(128)


def test_serve_rehearsal():
    # served from the second CPU device, as the chip serves beside the
    # host's: on the first one the key's fold queues behind the toy ticks.
    # The limit is ten times the chip's, for a host that six workers share
    with jax.default_device(jax.local_devices(backend="cpu")[1]):
        out = chip_smoke.phase_serve(
            SERVE_TOY, 4, 4, SERVE_REQUESTS,
            submit_limit_ms=10 * chip_smoke.SUBMIT_LIMIT_MS)
    assert out["prefix_hit_tokens"] >= 16
    assert out["tokens"] == 8 + 8 + 6 + 10 + 8 + 6
    assert out["tick_kinds"]["mixed"] and out["tick_kinds"]["decode_only"]
    assert out["tick_temp_bytes"] > 0 and out["tick_alias_bytes"] > 0
    assert len(out["submits"]) == 6
    assert sum(1 for inflight, _ in out["submits"] if inflight) >= 2


def test_serve_looped_rehearsal():
    """The looped pass at a toy width: 2 layers run 4 times, 8 cache
    layers, tokens and exit steps against the float32 reference."""
    cfg = dataclasses.replace(
        GPTConfig.ouro_2_6b(), vocab_size=256, hidden_size=64, num_heads=4,
        num_layers=2, ffn_hidden_size=96, max_seq_len=128)
    out = chip_smoke.phase_serve_looped(cfg, 2, 4, 32)
    assert out["cache_layers"] == 8
    assert out["weights_bytes"] == 2 * cfg.num_params()
    assert out["worst"] <= chip_smoke.TOL_LOOP_SHORTFALL
    assert out["exit_gap"] <= chip_smoke.TOL_LOOP_EXIT


def test_latent_rehearsal():
    """The latent-attention pass at toy sizes: the pools' reads against
    float32 spellings, a small model's tokens and selections against the
    float32 reference, the windowed pages given back."""
    import jax.numpy as jnp

    from paddle_tpu.models.dots3 import Dots3Config

    out = chip_smoke.phase_latent(
        Dots3Config.tiny(experts_held=(0, 4)), 3, 4, 24,
        [(2, 5, 3, 12, 8, 4, 10, 6, 5, jnp.bfloat16),
         (3, 1, 3, 12, 8, 4, 10, 6, 5, jnp.bfloat16)])
    # off the chip the tick's selected attention is the XLA spelling, and
    # the kernel ran interpreted beside it
    assert set(out["tick_paths"]) == {"xla"}
    assert out["selected_pallas"] <= chip_smoke.TOL_LATENT_OPS
    assert out["median"] <= out["worst"] <= chip_smoke.TOL_LATENT_SHORTFALL
    assert out["differ"] <= chip_smoke.TOL_LATENT_SELECTED
    assert out["freed"] > 0
    assert max(out["index"], out["selected"], out["window"]) \
        <= chip_smoke.TOL_LATENT_OPS


def test_dsv2_rehearsal():
    """DeepSeek-V2's pass at toy sizes: the dense latent attention through
    both spellings against a float32 softmax, a small model's tokens (two
    chunks a tick) against the float32 reference."""
    import jax.numpy as jnp

    from paddle_tpu.models.deepseek_v2 import DeepseekV2Config

    out = chip_smoke.phase_dsv2(
        DeepseekV2Config.tiny(experts_held=(4, 4)), 3, 4, 24,
        [(2, 5, 3, 12, 8, 4, 10, jnp.bfloat16),
         (3, 1, 3, 12, 8, 4, 10, jnp.bfloat16)])
    # off the chip the tick's attention is the XLA walk, and the kernel ran
    # interpreted beside it
    assert set(out["tick_paths"]) == {"xla"}
    assert max(out["dense"], out["dense_pallas"]) <= chip_smoke.TOL_LATENT_OPS
    assert out["median"] <= out["worst"] <= chip_smoke.TOL_LATENT_SHORTFALL
    cfg = DeepseekV2Config.tiny(experts_held=(4, 4))
    assert out["weights_bytes"] == 2 * cfg.num_params()


def test_olmoh_rehearsal():
    """Olmo-Hybrid's pass at toy sizes: the served delta rule against its
    spellings (off the chip: the spellings themselves), a small model's
    tokens through K/V pages and a state a slot against the float32
    reference."""
    from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig

    cfg = OlmoHybridConfig.tiny(initializer_range=0.05)
    out = chip_smoke.phase_olmoh(cfg, 3, 4, 24, 8, (6, 24, 48, 5, 128),
                                 "xla", requests=((23, 12), (41, 10),
                                                  (7, 16)))
    assert out["tick_paths"] == {"step": ["xla"], "chunk": ["xla"],
                                 "prep": ["xla"]}
    assert max(out["step_o"], out["step_s"], out["chunk_o"],
               out["chunk_s"]) <= chip_smoke.TOL_GDN_OPS
    assert out["prep_step"] == out["prep_chunk"] == 0   # the spelling itself
    assert out["median"] <= out["worst"] <= chip_smoke.TOL_GDN_WORST
    assert out["weights_bytes"] == 2 * cfg.num_params()


def test_a_submit_behind_the_ticks_in_flight_fails_the_serve_phase():
    limit = chip_smoke.SUBMIT_LIMIT_MS
    # with nothing in flight a slow submit proves nothing
    chip_smoke.check_submits([(0, 9 * limit), (3, 0.1), (2, limit)], limit)
    with pytest.raises(chip_smoke.SmokeFailure, match="submit"):
        chip_smoke.check_submits([(0, 0.1), (3, 0.2), (2, 1.1 * limit),
                                  (3, 0.1)], limit)


@pytest.mark.parametrize("temp,alias,what", [
    (2.4e9, 4.8e9, "temporaries"),      # one whole pool copied
    (4.9e9, 4.8e9, "temporaries"),      # both, as the whole-tick cond did
    (1e6, 2.4e9, "aliases"),            # one pool not updated in place
    (1e6, 0.0, "aliases")])
def test_a_copied_pool_fails_the_serve_phase(temp, alias, what):
    pools = [2.4e9, 2.4e9]
    chip_smoke.check_tick_memory(1e6, 4.8e9, pools)
    chip_smoke.check_tick_memory(0.0, 4.9e9, pools + [1e6, 1e6])
    with pytest.raises(chip_smoke.SmokeFailure, match=what):
        chip_smoke.check_tick_memory(temp, alias, pools)


def test_train_and_multichip_rehearsal():
    loss0 = chip_smoke.phase_train(TOY, micro=2, n_micro=2,
                                   steps=3)["loss0"]
    # float32: XLA:CPU aborts on the bf16 all-reduce of the head's dx over
    # tp (the trainer keeps its head outside the region on CPU + amp)
    chip_smoke.phase_multichip(TOY, 2, 2, loss0, TOY, zero_batch=8,
                               head=(4, 512, 32, 96, True, jnp.float32))


def test_import_starts_no_backend():
    """One process per chip: whoever imports the package (a launcher, a
    parent of workers) must not take the accelerator by doing so."""
    code = ("import paddle_tpu, paddle_tpu.serving, "
            "paddle_tpu.distributed.launch, chip_smoke\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]


def test_compile_cache_placement(monkeypatch, keep_cache_config):
    from paddle_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == keep_cache_config
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_ling_rehearsal():
    """Ling-3.0's pass at toy sizes: the served per-channel rule against its
    spellings (off the chip: the spellings themselves), a small model's
    tokens through a state a slot beside latent pages against the float32
    reference."""
    from paddle_tpu.models.ling3 import Ling3Config

    cfg = Ling3Config.tiny(initializer_range=0.05, experts_held=(4, 8))
    out = chip_smoke.phase_ling(cfg, 3, 4, 24, 8, (4, 16, 16, 5, 128),
                                "xla", requests=((23, 12), (41, 10),
                                                 (7, 16)))
    assert out["tick_paths"] == {"step": ["xla"], "chunk": ["xla"],
                                 "prep": ["xla"], "latent": ["xla"]}
    assert max(out["step_o"], out["step_s"], out["chunk_o"],
               out["chunk_s"]) <= chip_smoke.TOL_GDN_OPS
    assert out["prep_step"] == out["prep_chunk"] == 0   # the spelling itself
    assert out["median"] <= out["worst"] <= chip_smoke.TOL_GDN_WORST
    assert out["weights_bytes"] == 2 * cfg.num_params()


def test_falcon_rehearsal():
    """Falcon-H1's pass at toy sizes: the served state-space rule against its
    references (off the chip: the spellings themselves), grouped attention
    against its spelling, a small model's tokens through a state a slot and
    grouped K/V pages in every layer against the float32 reference."""
    from paddle_tpu.models.falcon_h1 import FalconH1Config

    cfg = FalconH1Config.tiny(initializer_range=0.05)
    out = chip_smoke.phase_falcon(
        cfg, 3, 4, 24, 8, (4, 8, 16, 2, 5, 128),
        [(3, 1, 4, 2, 16, 4, 24, 37)], "xla",
        requests=((23, 12), (41, 10), (7, 16)),
        ragged=[(5, 1, 4, 2, 16, 4, 24)])
    assert out["tick_paths"] == {"step": ["xla"], "chunk": ["xla"],
                                 "prep": ["xla"], "attn": ["xla"]}
    assert max(out["step_y"], out["step_s"], out["chunk_y"],
               out["chunk_s"]) <= chip_smoke.TOL_GDN_OPS
    assert out["prep_step"] == out["prep_chunk"] == 0   # the spelling itself
    assert out["attn_3x1"] == out["ragged_5x1x4"] == 0
    assert out["median"] <= out["worst"] <= chip_smoke.TOL_GDN_WORST
    assert out["weights_bytes"] == 2 * cfg.num_params()


def test_laguna_rehearsal():
    """Laguna's pass at toy sizes: the windowed grouped attention against
    its spelling (off the chip: the spelling itself), a small model's tokens
    through full and windowed grouped K/V pages and held experts against the
    float32 reference, pages freed behind the window."""
    from paddle_tpu.models.laguna import LagunaConfig

    cfg = LagunaConfig.tiny(initializer_range=0.05)
    out = chip_smoke.phase_laguna(
        cfg, 3, 4, 24, 8, [(3, 1, 12, 2, 16, 4, 24, 37, 6),
                           (2, 8, 18, 2, 16, 4, 24, 40, 6)], "xla",
        requests=((23, 8), (41, 6)),
        ragged=[(5, 1, 12, 2, 16, 4, 24), (4, 8, 18, 2, 16, 4, 24, 6)])
    assert out["tick_paths"] == {"attn": ["xla"]}
    assert out["attn_3x1x12"] == out["attn_2x8x18"] == 0
    assert out["ragged_5x1x12"] == out["ragged_4x8x18"] == 0
    assert out["median"] <= out["worst"] <= chip_smoke.TOL_GDN_WORST
    assert out["weights_bytes"] == 2 * cfg.num_params()
    assert out["window_pages_freed"] > 0


@pytest.mark.parametrize("t,heads,window", [
    (1, 10, None), (1, 18, 300), (8, 10, None), (8, 18, 300)])
def test_the_ragged_check_of_the_grouped_kernel(attention_spelling,
                                                monkeypatch, t, heads,
                                                window):
    """``check_grouped_attention_ragged`` with the kernel interpreted: slots
    of 640 positions in pages of 4, so every edge of its list (a page's, a
    block's at 256 and 512, the capacity) is some row's last position; the
    kernel passes, and a kernel that hands a key/value head's queries out
    one row on is caught and its worst row named."""
    from paddle_tpu.ops import paged_attention as pa

    attention_spelling("pallas")
    shape = (14, t, heads, 2, 16, 4, 160, window)
    err = chip_smoke.check_grouped_attention_ragged(*shape, draws=2)
    assert 0 < err <= chip_smoke.TOL_RAGGED
    operand = pa._grouped_operand
    monkeypatch.setattr(pa, "_grouped_operand", lambda *a: jnp.roll(
        operand(*a), 1, axis=2))
    with pytest.raises(chip_smoke.SmokeFailure, match=r"draw 0 .* row \d+ "):
        chip_smoke.check_grouped_attention_ragged(*shape, draws=1)


def test_ragged_rows_hold_every_edge_of_the_walk():
    import numpy as np

    rng = np.random.default_rng(0)
    for window, t in ((None, 1), (512, 32)):
        last, pos0, tl, table = chip_smoke.ragged_rows(rng, 38, t, 16, 448,
                                                       window)
        assert {1, 15, 16, 255, 256, 257, 511, 512, 513, 7168} <= set(
            last.tolist())
        empty = np.flatnonzero(last == 0)
        assert (np.diff(empty) == 1).any() and not tl[empty].any()
        assert ((pos0 + tl == last) & (tl <= t)).all()
        assert (t == 1) or ((tl < np.minimum(last, t)) & (tl > 0)).any()
        ids = table[table > 0]
        assert len(set(ids.tolist())) == len(ids)       # a page, one owner
        pages = (table > 0).sum(axis=1)
        first = 0 if window is None else np.maximum(pos0 - window + 1, 0) // 16
        assert (pages == -(-last // 16) - np.where(last > 0, first, 0)).all()


@pytest.mark.parametrize("counted,groups,path,fault", [
    ({(5, 16): 3, (320, 320): 3}, [5], "pallas", None),
    ({(6, 16): 2, (9, 16): 4, (192, 192): 1}, [6, 9], "pallas", None),
    # a decode row's heads a tile each: the layout of before ISSUE 61
    ({(5, 80): 3, (320, 320): 3}, [5], "pallas", "not one tile"),
    ({(6, 16): 2, (9, 144): 4}, [6, 9], "pallas", "not one tile"),
    # no decode row's call counted at all
    ({(192, 192): 1}, [6], "pallas", "not one tile"),
    # off the chip nothing takes the kernel, and nothing may be counted
    ({}, [5], "xla", None), ({(5, 16): 1}, [5], "xla", "off the kernel"),
])
def test_the_operand_check_holds_a_decode_rows_queries_to_one_tile(
        counted, groups, path, fault):
    """``check_decode_operand``: what phases ``falcon`` and ``laguna`` hold
    ``serving/grouped_attn_operand{queries=,rows=}`` to."""
    from paddle_tpu.profiler import registry

    before = chip_smoke.grouped_operands()
    for (queries, rows), n in counted.items():
        registry().counter("serving/grouped_attn_operand{queries=%d,rows=%d}"
                           % (queries, rows)).add(n)
    if fault is None:
        chip_smoke.check_decode_operand(before, groups, path, "test")
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match=fault):
            chip_smoke.check_decode_operand(before, groups, path, "test")


@pytest.mark.parametrize("counted,fault", [
    ({(128, 512, 128): 12, (128, 128, 512): 6}, None),
    # a product that went by the kernel and counted no tile
    ({(128, 512, 128): 12}, "not"),
    # a contraction cut into steps: the powers of two's walk
    ({(128, 512, 128): 12, (128, 128, 512): 6, (128, 256, 128): 1}, "not"),
    # off the chip nothing takes the kernel, and nothing may be counted
    ({}, "cpu"), ({(128, 512, 128): 1}, "cpu"),
])
def test_the_tile_check_holds_the_products_to_one_whole_contraction_each(
        monkeypatch, counted, fault):
    """``check_gmm_tiles``: what phases ``experts``, ``latent``, ``dsv2``
    and ``ling`` hold ``moe/grouped_matmul_tiles{tile=}`` to."""
    from paddle_tpu.profiler import registry

    if fault != "cpu":
        monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    registry().counter(
        "moe/grouped_matmul_tiles{tile=128x2048x1024}").add(2)  # an earlier
    before = chip_smoke.gmm_tiles()                             # phase's
    assert before[128, 2048, 1024] >= 2
    for tile, n in counted.items():
        registry().counter(
            "moe/grouped_matmul_tiles{tile=%dx%dx%d}" % tile).add(n)
    if fault is None or (fault == "cpu" and not counted):
        assert chip_smoke.check_gmm_tiles(before, 512, 128, "x") == counted
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match="whole"):
            chip_smoke.check_gmm_tiles(before, 512, 128, "x")
