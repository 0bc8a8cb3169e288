"""OLMoE through the one block definition (models/gpt.py), the drop-less
expert layer (distributed/moe.py dropless_moe) and HybridPipelineTrainer,
against the plain float32 reference (models/olmoe_reference.py) at a small
size on the CPU: hidden 64, 4 heads, 8 experts of width 32, top-2, 2
layers, float32, seeded weights."""
import contextlib
import difflib
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.distributed.moe import dropless_moe, switch_moe
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.models import gpt as gpt_mod
from paddle_tpu.models import olmoe_reference as ref
from paddle_tpu.static.functional import state_tensors

HEADS, TOP_K, EXPERTS = 4, 2, 8


def small_config(**kw):
    return GPTConfig(**{**dict(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=HEADS,
        max_seq_len=32, ffn_hidden_size=64, tie_word_embeddings=False,
        norm="rmsnorm", position="rope", qk_norm=True, bias=False,
        ffn="swiglu", moe_num_experts=EXPERTS, moe_top_k=TOP_K,
        moe_expert_width=32, moe_dropless=True, moe_aux_weight=0.01,
        moe_z_weight=0.001), **kw})


def weights_of(model):
    """(one dict a layer, the others) as the reference takes them."""
    names, tensors = state_tensors(model)[:2]
    layers = []
    for b in model.blocks:
        n, t = state_tensors(b)[:2]
        layers.append({k: np.asarray(v._value) for k, v in zip(n, t)})
    other = {n: np.asarray(t._value) for n, t in zip(names, tensors)
             if not n.startswith("blocks.")}
    return layers, other


@pytest.fixture(scope="module")
def small():
    paddle.seed(3)
    model = GPT(small_config())
    tokens = np.random.default_rng(0).integers(0, 128, (2, 32),
                                               dtype=np.int32)
    return model, tokens


def test_preset_carries_the_catalog_rows_widths():
    c = GPTConfig.olmoe_1b_7b()
    assert (c.hidden_size, c.num_layers, c.num_heads, c.max_seq_len,
            c.vocab_size) == (2048, 16, 16, 4096, 50304)
    assert (c.moe_num_experts, c.moe_top_k, c.moe_expert_width) == \
        (64, 8, 1024)
    assert c.ffn_hidden_size == c.moe_top_k * c.moe_expert_width
    assert (c.norm, c.position, c.qk_norm, c.bias, c.ffn) == \
        ("rmsnorm", "rope", True, False, "swiglu")
    assert not c.tie_word_embeddings and c.moe_dropless
    assert (c.layer_norm_eps, c.rope_theta) == (1e-5, 10000.0)
    assert (c.moe_aux_weight, c.moe_z_weight) == (0.01, 0.001)
    # 6.92 B parameters, 1.3 B active (model card)
    assert round(c.num_params() / 1e9, 2) == 6.92


def test_num_params_counts_the_parameters_built(small):
    model, _ = small
    built = sum(int(np.prod(p.shape)) for p in model.parameters())
    assert model.config.num_params() == built
    assert "embeddings.wpe.weight" not in state_tensors(model)[0]


def test_logits_match_the_reference(small):
    # float32 both sides; the orders of summation differ (fused QKV,
    # grouped experts against a masked loop): 1e-5 of the largest logit
    model, tokens = small
    got = np.asarray(model(paddle.to_tensor(tokens))._value)
    layers, other = weights_of(model)
    want, _, _ = ref.forward(layers, other, tokens, HEADS, TOP_K)
    np.testing.assert_allclose(got, np.asarray(want), rtol=0,
                               atol=1e-5 * float(np.abs(want).max()))


def test_loss_has_cross_entropy_and_both_auxiliary_terms(small):
    model, tokens = small
    layers, other = weights_of(model)
    want = ref.loss_terms(layers, other, tokens, HEADS, TOP_K)
    got = float(model.loss(paddle.to_tensor(tokens)).numpy())
    # float32 sums in another order: 1e-6 relative
    assert got == pytest.approx(want["loss"], rel=1e-6)
    # each term is really there: leaving one out moves the loss by more
    assert 0.01 * want["balance"] > 1e-3 * want["loss"]
    assert 0.001 * want["z"] > 1e-4 * want["loss"]
    cfg = model.config
    cfg.moe_z_weight = 0.0
    try:
        no_z = float(model.loss(paddle.to_tensor(tokens)).numpy())
    finally:
        cfg.moe_z_weight = 0.001
    assert no_z == pytest.approx(want["ce"] + 0.01 * want["balance"],
                                 rel=1e-6)


def test_parameter_gradients_match_the_reference(small):
    model, tokens = small
    layers, other = weights_of(model)

    def ref_loss(layers, other):
        lg, balance, z = ref.forward(layers, other, tokens, HEADS, TOP_K)
        return ref.next_token_loss(lg, tokens) + 0.01 * balance + 0.001 * z

    want_layers, want_other = jax.jit(jax.grad(ref_loss, argnums=(0, 1)))(
        [{k: jnp.asarray(v) for k, v in w.items()} for w in layers],
        {k: jnp.asarray(v) for k, v in other.items()})
    loss = model.loss(paddle.to_tensor(tokens))
    loss.backward()
    got = {n: np.asarray(p.grad._value)
           for n, p in zip(state_tensors(model)[0], model.parameters())}
    want = dict(want_other)
    for i, w in enumerate(want_layers):
        want.update({f"blocks.{i}.{k}": v for k, v in w.items()})
    assert set(got) == set(want)
    for name, g in got.items():
        # float32 on both sides: 2e-5 of the gradient's largest entry
        scale = float(np.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(g, np.asarray(want[name]), rtol=0,
                                   atol=2e-5 * scale, err_msg=name)


def test_rope_against_a_hand_written_case():
    # d = 4: pairs (0, 2) at frequency 1 and (1, 3) at theta**-0.5
    x = jnp.asarray(np.arange(1, 13, dtype=np.float32).reshape(1, 3, 1, 4))
    theta = 100.0
    got = np.asarray(gpt_mod.rope_rotate(x, theta))
    for p in range(3):
        a, b, c, d = np.asarray(x)[0, p, 0]
        f0, f1 = p * 1.0, p * theta ** -0.5
        want = [a * np.cos(f0) - c * np.sin(f0),
                b * np.cos(f1) - d * np.sin(f1),
                c * np.cos(f0) + a * np.sin(f0),
                d * np.cos(f1) + b * np.sin(f1)]
        np.testing.assert_allclose(got[0, p, 0], want, rtol=1e-6)
    np.testing.assert_allclose(got, np.asarray(ref.rope(x, theta)),
                               rtol=1e-6)
    # position 0 is left as it is, and a rotation keeps the norm
    np.testing.assert_array_equal(got[0, 0], np.asarray(x)[0, 0])
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-6)


def test_qk_norm_against_a_hand_written_case():
    """q and k are RMS-normalised over the whole projection, all heads
    wide, before the heads are split: with identity-like weights the
    attention's q has unit mean square over its 8 columns, not over a
    head's 4."""
    cfg = GPTConfig(vocab_size=16, hidden_size=8, num_layers=1, num_heads=2,
                    max_seq_len=4, norm="rmsnorm", position="rope",
                    qk_norm=True, bias=False, ffn="swiglu",
                    tie_word_embeddings=False, use_flash_attention=False)
    paddle.seed(0)
    attn = gpt_mod.GPTAttention(cfg)
    seen = {}
    attn._rotate = lambda q, k: (seen.setdefault("q", q),
                                 seen.setdefault("k", k))
    x = np.random.default_rng(1).normal(size=(1, 4, 8)).astype(np.float32)
    attn.q_norm.weight.set_value(np.full(8, 2.0, np.float32))
    attn(paddle.to_tensor(x))
    w = np.asarray(attn.qkv_proj.weight._value).reshape(8, 3, 8)
    for name, col, gain in (("q", 0, 2.0), ("k", 1, 1.0)):
        raw = x[0] @ w[:, col]                                  # [4, 8]
        want = gain * raw / np.sqrt((raw ** 2).mean(-1, keepdims=True)
                                    + cfg.layer_norm_eps)
        got = np.asarray(seen[name]._value).reshape(4, 8)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_dense_swiglu_ffn_against_a_hand_written_case():
    cfg = GPTConfig(vocab_size=16, hidden_size=8, num_layers=1, num_heads=2,
                    max_seq_len=4, ffn_hidden_size=12, bias=False,
                    ffn="swiglu")
    paddle.seed(0)
    mlp = gpt_mod.GPTMLP(cfg)
    x = np.random.default_rng(2).normal(size=(1, 4, 8)).astype(np.float32)
    gate, up, down = (np.asarray(l.weight._value)
                      for l in (mlp.fc_gate, mlp.fc_in, mlp.fc_out))
    g = x @ gate
    want = ((g / (1 + np.exp(-g))) * (x @ up)) @ down
    np.testing.assert_allclose(np.asarray(mlp(paddle.to_tensor(x))._value),
                               want, rtol=1e-5, atol=1e-6)
    assert mlp.fc_in.bias is None and mlp.fc_out.bias is None


def _one_layer(t=256, h=16, f=8, e=EXPERTS, bias_to=None, seed=5):
    r = np.random.default_rng(seed)
    x = r.normal(size=(t, h)).astype(np.float32)
    router = r.normal(size=(h, e)).astype(np.float32) * 0.1
    if bias_to is not None:
        # a router that prefers one expert whatever the token: its column
        # is aligned with the tokens' common direction
        x = x + 2.0
        router[:, bias_to] += 1.0
    w = {"mlp.gate": router,
         "mlp.w_gate": r.normal(size=(e, h, f)).astype(np.float32) * 0.3,
         "mlp.w_up": r.normal(size=(e, h, f)).astype(np.float32) * 0.3,
         "mlp.w_down": r.normal(size=(e, f, h)).astype(np.float32) * 0.3}
    return x, w


@pytest.mark.parametrize("bias_to", [None, 3])
def test_dropless_moe_matches_the_reference_forward_and_gradients(bias_to):
    x, w = _one_layer(bias_to=bias_to)
    args = (x, w["mlp.gate"], w["mlp.w_gate"], w["mlp.w_up"],
            w["mlp.w_down"])

    def got_fn(*a):
        y, balance, z, _ = dropless_moe(*a, top_k=TOP_K)
        return jnp.sum(y * y) + balance + z

    def want_fn(x, gate, w_gate, w_up, w_down):
        y, balance, z = ref.moe(x, {"mlp.gate": gate, "mlp.w_gate": w_gate,
                                    "mlp.w_up": w_up, "mlp.w_down": w_down},
                                TOP_K)
        return jnp.sum(y * y) + balance + z

    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(got_fn, argnums=range(5))(*args)
        want, want_g = jax.value_and_grad(want_fn, argnums=range(5))(*args)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, wg in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wg), rtol=0,
                                   atol=2e-5 * float(np.abs(wg).max()))


def test_a_forced_imbalance_drops_nothing_where_switch_moe_drops():
    """One expert is every token's first choice: ``dropless_moe`` gives
    every token both its experts, ``switch_moe`` at capacity 1.25 lets
    most of that expert's tokens fall through."""
    t = 256
    x, w = _one_layer(t=t, bias_to=3)
    logits = x @ w["mlp.gate"]
    first = logits.argmax(-1)
    assert (first == 3).mean() > 0.9            # over four times its share

    y, _, _, rows = dropless_moe(x, w["mlp.gate"], w["mlp.w_gate"],
                                 w["mlp.w_up"], w["mlp.w_down"], top_k=TOP_K)
    want, _, _ = ref.moe(jnp.asarray(x), {k: jnp.asarray(v)
                                          for k, v in w.items()}, TOP_K)
    # every token's output is the reference's, which has no capacity
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    rows = np.asarray(rows)
    assert rows.sum() == t * TOP_K and rows[3] > 0.9 * t

    # switch_moe, same router, same tokens, GELU experts: its capacity is
    # ceil(1.25 * 2 * 256 / 8) = 80 rows an expert, expert 3 is asked for
    # over 230, so at least 150 tokens lose their first expert
    e, h, f = w["mlp.w_gate"].shape
    y_sw, _ = switch_moe(x, w["mlp.gate"], w["mlp.w_gate"],
                         np.zeros((e, f), np.float32), w["mlp.w_down"],
                         np.zeros((e, h), np.float32), top_k=TOP_K,
                         capacity_factor=1.25)
    y_all, _ = switch_moe(x, w["mlp.gate"], w["mlp.w_gate"],
                          np.zeros((e, f), np.float32), w["mlp.w_down"],
                          np.zeros((e, h), np.float32), top_k=TOP_K,
                          capacity_factor=float(e))
    lost = np.abs(np.asarray(y_sw) - np.asarray(y_all)).max(-1) > 1e-6
    assert lost.sum() >= 150


def _trainer(model, pp, n_micro):
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh

    mesh = create_mesh({"dp": 1, "pp": pp, "tp": 1, "sp": 1},
                       jax.devices()[:pp])
    opt = paddle.optimizer.SGD(0.0, parameters=model.parameters())
    return HybridPipelineTrainer(model, opt, DistributedStrategy(), mesh,
                                 n_micro=n_micro)


@pytest.mark.parametrize("pp", [1, 2])
def test_trainer_steps_the_small_olmoe_with_the_unpipelined_loss(pp):
    """The trainer's loss is the model's: cross entropy plus both weighted
    auxiliary terms through the stage_aux carry, one micro-batch routed at
    a time and the micro-batches' terms averaged."""
    paddle.seed(3)
    model = GPT(small_config())
    tokens = np.random.default_rng(7).integers(0, 128, (4, 32),
                                               dtype=np.int32)
    want = np.mean([float(model.loss(paddle.to_tensor(tokens[i:i + 2]))
                          .numpy()) for i in (0, 2)])
    layers, other = weights_of(model)
    by_ref = np.mean([ref.loss_terms(layers, other, tokens[i:i + 2], HEADS,
                                     TOP_K)["loss"] for i in (0, 2)])
    assert want == pytest.approx(by_ref, rel=1e-6)
    tr = _trainer(model, pp, n_micro=2)
    got = float(tr.step(tokens))
    # float32, the fused head's chunked sums in another order
    assert got == pytest.approx(want, rel=2e-6)


@pytest.mark.parametrize("pp", [1, 2])
def test_a_step_hands_out_what_it_routed(pp):
    """``aux_stats`` are outputs of the step itself, summed over its layers
    and micro-batches: every assignment has a row, at pp = 1 and across
    the pipeline's stages, and the program holds no callback."""
    tokens = np.random.default_rng(7).integers(0, 128, (4, 32),
                                               dtype=np.int32)
    tr = _trainer(GPT(small_config()), pp, n_micro=2)
    assert "callback" not in tr.aot_lower(tokens).as_text()
    tr.step(tokens)
    stats = jax.device_get(tr.aux_stats)
    layers = tr.model.config.num_layers
    assigned = tokens.size * TOP_K * layers
    assert stats["moe/assigned"] == assigned
    assert stats["moe/rows"].shape == (EXPERTS,)
    assert stats["moe/rows"].sum() == assigned
    # the fullest expert of each call, summed over the calls
    calls = layers * 2
    assert assigned / EXPERTS <= stats["moe/load_max"] <= \
        calls * stats["moe/rows"].max()


def test_a_profiled_step_feeds_the_expert_gauges():
    """``profiler.summary()`` reads the two gauges after any step taken
    with the profiler on, from the step's outputs: one program, whether the
    profiler was on when it was traced or not."""
    tokens = np.random.default_rng(7).integers(0, 128, (4, 32),
                                               dtype=np.int32)
    profiler.reset()
    tr = _trainer(GPT(small_config()), 1, n_micro=2)
    tr.step(tokens)
    assert "moe/dropped_tokens" not in profiler.summary()["metrics"]
    profiler.enable()
    try:
        tr.step(tokens)
        seen = profiler.summary()["metrics"]
    finally:
        profiler.disable()
    assert seen["moe/dropped_tokens"]["value"] == 0
    assert 1.0 <= seen["moe/expert_load_max_over_mean"]["value"] < EXPERTS


def test_trainer_learns_the_small_olmoe():
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh

    paddle.seed(4)
    model = GPT(small_config())
    opt = paddle.optimizer.AdamW(3e-3, parameters=model.parameters())
    s = DistributedStrategy()
    s.recompute = True
    tr = HybridPipelineTrainer(
        model, opt, s, create_mesh({"dp": 1, "pp": 1, "tp": 1, "sp": 1},
                                   jax.devices()[:1]), n_micro=2)
    tokens = np.random.default_rng(8).integers(0, 128, (4, 32),
                                               dtype=np.int32)
    losses = [float(tr.step(tokens)) for _ in range(6)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_serving_entry_points_refuse_the_new_block_kinds(small):
    model, tokens = small
    with pytest.raises(NotImplementedError, match="GPTBlock.s experts are not yet"):
        model.generate(paddle.to_tensor(tokens[:, :4]), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="GPTBlock.s experts are not yet"):
        gpt_mod._gpt_decode_state(model)
    with pytest.raises(NotImplementedError, match="GPTBlock.s experts are not yet"):
        gpt_mod.gpt_cached_apply(model.config, {}, {}, None, None,
                                 jnp.zeros((1, 1), jnp.int32), 0)
    with pytest.raises(NotImplementedError, match="GPTBlock.s experts are not yet"):
        gpt_mod.gpt_ragged_apply(model.config, {}, {}, None,
                                 *[None] * 7, decode_rows=0, chunk_width=1)
    from paddle_tpu.serving import ServingConfig, ServingEngine
    with pytest.raises(NotImplementedError, match="GPTBlock.s experts are not yet"):
        ServingEngine(model, ServingConfig(num_slots=1, page_size=4,
                                           pages_per_slot=4))


def test_unknown_block_kinds_are_refused():
    with pytest.raises(ValueError, match="unknown block kind"):
        GPTConfig(norm="batchnorm")
    with pytest.raises(ValueError, match="bias-free SwiGLU"):
        GPTConfig(moe_num_experts=4, moe_dropless=True)


# The dense GPT's programs, lowered at a small size from models/gpt.py and,
# in the same process, from a frozen copy of that file as it stood before the
# architecture fields (tests/frozen/gpt_pr25.py): equal text. An accidental
# change, by a field that leaks into GPT's path, fails here with the lines
# that moved; jax's own printing and conftest's settings are the same on both
# sides whatever they are.
@contextlib.contextmanager
def frozen_gpt():
    """``paddle_tpu.models.gpt`` answered by the frozen copy: the trainer
    and the engine take the model they are given. The frozen ``GPT`` predates
    the served model's protocol (``models/tick.py``) and is given its two
    missing methods here, over its own forward."""
    import paddle_tpu.models as models

    name = "paddle_tpu.models.gpt"
    spec = importlib.util.spec_from_file_location(name, os.path.join(
        os.path.dirname(__file__), "frozen", "gpt_pr25.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # PR 28 handed the tick its page pools as one argument
    # (``paged_cache.Pools``); the frozen forward takes and returns them
    # apart, so it is called through that signature here. It knows no
    # ``has_chunks`` (PR 32): it runs every row's attention every tick
    apart = mod.gpt_ragged_apply

    def ragged_apply(self, stacked, other, pools, *a, has_chunks=None, **kw):
        logits, k, v = apart(self.config, stacked, other, pools.k, pools.v,
                             *a, **kw)
        return logits, pools._replace(k=k, v=v), {}

    mod.GPT.ragged_apply = ragged_apply
    mod.GPT.cache_spec = lambda self: {
        "kind": "kv", "layers": self.config.num_layers,
        "heads": self.config.num_heads,
        "head_dim": self.config.hidden_size // self.config.num_heads}
    live = sys.modules[name]
    sys.modules[name] = models.gpt = mod
    try:
        yield mod
    finally:
        sys.modules[name] = models.gpt = live


def _same_text(live: str, frozen: str):
    diff = list(difflib.unified_diff(frozen.splitlines(), live.splitlines(),
                                     "frozen", "live", lineterm="", n=1))
    assert not diff, "\n".join(diff[:80])


def dense_gpt_step_text(gpt):
    paddle.seed(0)
    model = gpt.GPT(gpt.GPTConfig(vocab_size=128, hidden_size=64,
                                  num_layers=2, num_heads=4, max_seq_len=32))
    tr = _trainer(model, 1, n_micro=2)
    return tr.aot_lower(jax.ShapeDtypeStruct((4, 32), np.int32)).as_text()


def test_the_new_fields_leave_the_dense_gpt_step_as_it_was():
    import paddle_tpu.models.gpt as live

    with frozen_gpt() as frozen:
        was = dense_gpt_step_text(frozen)
    _same_text(dense_gpt_step_text(live), was)


def dense_gpt_served(gpt):
    """Three requests through two slots (chunks, decodes, ticks with and
    without a chunk, a slot reused): each request's tokens and the pools
    at the end."""
    from paddle_tpu.serving import ServingConfig, ServingEngine

    paddle.seed(0)
    model = gpt.GPT(gpt.GPTConfig(vocab_size=128, hidden_size=64,
                                  num_layers=2, num_heads=4, max_seq_len=32))
    model.eval()
    eng = ServingEngine(model, ServingConfig(num_slots=2, page_size=4,
                                             pages_per_slot=8))
    rng = np.random.default_rng(5)
    rids = [eng.submit(rng.integers(0, 128, n, dtype=np.int32), 6)
            for n in (5, 19, 9)]
    out = eng.run()
    return [out[r] for r in rids], eng.pool.pools


@pytest.mark.parametrize("what", ["tokens", "k", "v"])
def test_the_carried_pools_serve_what_the_sliced_pools_served(what):
    """ISSUE 32 moved the tick's pools from the layer scan's xs -> ys (a
    layer sliced out and written back a step, under a whole-tick ``cond``)
    to its carry, indexed by layer in place. The frozen copy still holds the
    old forward: the same requests get the same tokens and leave the same
    keys and values in every page but the null page (which a tick without a
    chunk now writes its pad rows to), bit for bit."""
    import paddle_tpu.models.gpt as live

    with frozen_gpt() as frozen:
        was_tokens, was_pools = dense_gpt_served(frozen)
    tokens, pools = dense_gpt_served(live)
    if what == "tokens":
        for got, want in zip(tokens, was_tokens):
            np.testing.assert_array_equal(got, want)
    else:
        got, want = (np.asarray(getattr(p, what))[:, 1:]
                     for p in (pools, was_pools))
        assert want.any()
        np.testing.assert_array_equal(got, want)
