"""ISSUE 33: a looped language model (Ouro: the same layers run ``loop_steps``
times, sandwich norms, RoPE, SwiGLU, an exit gate after every step) served
by ``ServingEngine`` through a cache for every (loop step, layer), against
the plain float32 reference (models/ouro_reference.py) at a small width on
seeded weights, controls included."""
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.models import gpt as gpt_mod
from paddle_tpu.models import ouro_reference as ref
from paddle_tpu.profiler import recompile, registry
from paddle_tpu.serving import ServingConfig, ServingEngine, SpecConfig

VOCAB, HEADS = 128, 4
STEPS = (1, 2, 4)


def _config(steps=4, threshold=0.5, **kw):
    return GPTConfig(**{**dict(
        vocab_size=VOCAB, hidden_size=64, num_layers=3, num_heads=HEADS,
        max_seq_len=64, ffn_hidden_size=96, layer_norm_eps=1e-6,
        # wide enough weights that the attention is far from uniform and
        # the gate far from one half: what the controls must move
        initializer_range=0.15, tie_word_embeddings=False, norm="rmsnorm",
        position="rope", rope_theta=1e6, bias=False, ffn="swiglu", sandwich_norm=True,
        loop_steps=steps, exit_threshold=threshold), **kw})


def _net(steps=4, threshold=0.5, seed=0, **kw):
    paddle.seed(seed)
    net = GPT(_config(steps, threshold, **kw))
    net.eval()
    return net


def _weights(net):
    """The reference's arguments from the weights the model holds; a
    one-step model has no gate, and the reference is handed a closed one."""
    stacked, other = net._decode_state()
    other = dict(other)
    if "exit_gate.weight" not in other:
        other["exit_gate.weight"] = jnp.zeros((64, 1))
        other["exit_gate.bias"] = jnp.zeros((1,))
    n = net.config.num_layers

    def layers():
        for i in range(n):
            yield {k: v[i] for k, v in stacked.items()}

    return layers, other


def _reference(net, tokens, steps=None, control=None):
    cfg = net.config
    layers, other = _weights(net)
    out = ref.forward(layers, other, tokens, HEADS,
                      steps or cfg.loop_steps, cfg.exit_threshold,
                      cfg.layer_norm_eps, cfg.rope_theta, control)
    out["logits"] = np.asarray(ref.logits(out["state"], other))
    return out


def _prompts(lens, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n, dtype=np.int32) for n in lens]


def _serve(net, prompts, new=6, **kw):
    """Requests through two slots with a chunk of 8: chunked prefill, ticks
    that mix a chunk with decode rows, ticks without a chunk, a slot
    reused. Returns the engine and each request's id."""
    cfg = dict(num_slots=2, page_size=4, pages_per_slot=8, prefill_chunk=8)
    cfg.update(kw)
    eng = ServingEngine(net, ServingConfig(**cfg))
    rids = [eng.submit(p, new) for p in prompts]
    eng.run()
    return eng, rids


def _judge(net, eng, rids, prompts, steps=None, control=None):
    """The engine's tokens and exit steps against one full forward of the
    reference over prompt and output together: the worst shortfall of an
    emitted token's logit below its position's maximum, the largest
    difference of a request's mean expected exit step, and whether every
    request's chosen steps are the reference's."""
    worst, exit_gap, chosen_same = 0.0, 0.0, True
    for rid, prompt in zip(rids, prompts):
        out = np.asarray(eng.tokens_so_far(rid), np.int32)
        seq = np.concatenate([prompt, out[:-1]])[None]
        r = _reference(net, seq, steps, control)
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        lg = r["logits"][0, at]
        worst = max(worst, float((lg.max(-1) - lg[np.arange(len(out)),
                                                  out]).max()))
        expected, chosen, n = eng.exit_steps(rid)
        assert n == len(out)
        exit_gap = max(exit_gap, abs(
            expected - float(np.asarray(r["expected"])[0, at].mean())))
        chosen_same &= bool(np.isclose(
            chosen, float(np.asarray(r["chosen"])[0, at].mean())))
    return worst, exit_gap, chosen_same


def test_the_preset_counts_the_published_parameters():
    cfg = GPTConfig.ouro_2_6b()
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert cfg.num_params() == 48 * layer + 2 * 49152 * 2048 + 4097 \
        == 2_667_974_657
    assert not cfg.is_gpt3_block() and GPTConfig.gpt3_1_3b().is_gpt3_block()
    small = _net()
    assert small.config.num_params() == sum(
        int(np.prod(p.shape)) for p in small.parameters())
    with pytest.raises(ValueError, match="loop_steps"):
        GPTConfig(loop_steps=0)


def test_rope_at_positions_is_rope_rotate():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 7, 3, 16)),
                    jnp.float32)
    want = gpt_mod.rope_rotate(x, 1e6)
    np.testing.assert_array_equal(
        gpt_mod.rope_at(x, jnp.arange(7), 1e6), want)
    # one position a row, as the tick has them
    flat = jnp.swapaxes(x, 0, 1)[:, :1]                     # [7, 1, 3, 16]
    np.testing.assert_array_equal(
        gpt_mod.rope_at(flat, jnp.arange(7)[:, None], 1e6)[:, 0],
        want[0])


@pytest.mark.parametrize("threshold", [0.3, 0.5, 1.0])
def test_the_exit_rule_is_the_references(threshold):
    rng = np.random.default_rng(1)
    gates = jnp.asarray(rng.uniform(0.05, 0.7, (4, 5, 9)), jnp.float32)
    states = jnp.asarray(rng.normal(size=(4, 5, 9, 8)), jnp.float32)
    state, expected, chosen = gpt_mod.loop_exit(states, gates, threshold)
    want = ref.exit_rule(states, gates, threshold)
    np.testing.assert_array_equal(state, want[0])
    np.testing.assert_allclose(expected, want[1], rtol=1e-6)
    np.testing.assert_array_equal(chosen, want[2])
    if threshold == 1.0:        # the published value: every position at T
        assert (np.asarray(chosen) == 4).all()
        np.testing.assert_array_equal(state, states[3])
    else:
        assert len(np.unique(np.asarray(chosen))) > 1


@pytest.mark.parametrize("steps", STEPS)
def test_the_eager_forward_is_the_reference(steps):
    net = _net(steps)
    toks = np.stack(_prompts((12, 12)))
    got = np.asarray(net(paddle.to_tensor(toks))._value)
    np.testing.assert_allclose(got, _reference(net, toks)["logits"],
                               atol=2e-4)
    if steps > 1:
        expected, chosen = net.exit_steps(paddle.to_tensor(toks))
        r = _reference(net, toks)
        np.testing.assert_allclose(expected._value, r["expected"],
                                   atol=1e-4)
        np.testing.assert_array_equal(chosen._value, r["chosen"])


@pytest.mark.parametrize("steps", STEPS)
def test_mixed_ticks_serve_the_references_tokens(steps):
    """Chunked prefill then decode through ``steps * L`` caches equals one
    full forward: every emitted token is the reference's argmax at its
    position, and a looped model's exit steps are the reference's."""
    net = _net(steps)
    prompts = _prompts((5, 19, 9))
    eng, rids = _serve(net, prompts)
    assert eng.pool.k.shape[0] == steps * 3
    assert recompile.trace_counts()[eng.compiled_sites[0]] == 1
    worst, exit_gap, chosen_same = _judge(net, eng, rids, prompts)
    assert worst < 1e-3
    if steps > 1:
        assert exit_gap < 1e-4 and chosen_same
        assert 1.0 < eng.exit_steps()[0] < steps
    else:
        assert eng.exit_steps() == (0.0, 0.0, 0)


@pytest.mark.parametrize("control", ["three_steps", "shared_cache",
                                     "unrotated_keys"])
def test_a_wrong_model_is_told_apart(control):
    """What the check's limits must separate, at a small width: the tokens
    a right engine served, judged by a reference that runs three steps of
    four, reads step 1's cache at every step, or attends to unrotated
    keys. At the published threshold, where the head reads the last step."""
    net = _net(4, threshold=1.0)
    prompts = _prompts((5, 19, 9))
    eng, rids = _serve(net, prompts, new=12)
    right = _judge(net, eng, rids, prompts)
    wrong = _judge(net, eng, rids, prompts,
                   steps=3 if control == "three_steps" else None,
                   control=None if control == "three_steps" else control)
    assert right[0] < 1e-3 and right[1] < 1e-4
    assert wrong[0] > 0.05 and wrong[0] > 50 * right[0]
    assert wrong[1] > 50 * right[1]


def test_generate_and_the_engine_agree():
    net = _net(4)
    prompts = np.stack(_prompts((6, 6)))
    ids, _ = net.generate(paddle.to_tensor(prompts), max_new_tokens=6)
    eng, rids = _serve(net, list(prompts))
    for row, rid in zip(np.asarray(ids._value), rids):
        np.testing.assert_array_equal(row, eng.tokens_so_far(rid))
    paged, _ = net.generate(paddle.to_tensor(prompts), max_new_tokens=6,
                            paged=True)
    np.testing.assert_array_equal(paged._value, ids._value)
    sampled, _ = net.generate(paddle.to_tensor(prompts), max_new_tokens=6,
                              decode_strategy="sampling", top_k=8, seed=3)
    assert sampled.shape == [2, 6]


def test_a_prefix_hit_carries_every_cache_layer():
    """Two prompts share their first page: the second is served off the
    first's page, which holds all 12 cache layers, and gets the tokens and
    exit steps an engine without the prefix cache gives it."""
    net = _net(4)
    a, b = _prompts((11, 11))
    b[:4] = a[:4]
    hits = registry().counter("serving/prefix_hit_tokens")
    before = hits.value
    with_cache, r1 = _serve(net, [a, b], num_slots=1, prefix_cache=True)
    assert hits.value - before >= 4
    without, r2 = _serve(net, [a, b], num_slots=1, prefix_cache=False)
    for x, y in zip(r1, r2):
        assert with_cache.tokens_so_far(x) == without.tokens_so_far(y)
        assert with_cache.exit_steps(x) == without.exit_steps(y)


def test_the_looped_tick_holds_no_pool_sized_temporary():
    """PR 32's property at 12 cache layers: the pools are the carry of the
    scan over steps and of the layer scan inside it, updated in place."""
    reg = registry()
    for name in ("tick_temp_bytes", "tick_alias_bytes"):
        reg.gauge("serving/" + name).set(-1.0)
    eng, _ = _serve(_net(4), _prompts((5, 12)), new=3, num_slots=3,
                    num_pages=1501)
    one_pool = eng.pool.k.nbytes
    weights = sum(a.nbytes for a in jax.tree.leaves(eng.served_weights()))
    assert one_pool > 4 * weights
    assert 0 <= reg.gauge("serving/tick_temp_bytes").value < one_pool
    assert reg.gauge("serving/tick_alias_bytes").value >= 2 * one_pool


def test_the_loops_names_reach_the_summary_and_the_program():
    reg = registry()
    steps_run = reg.counter("loop/steps_run").value
    ticks = reg.counter("serving/ticks").value
    eng, rids = _serve(_net(4), _prompts((5, 9)))
    metrics = profiler.summary()["metrics"]
    for name in ("serving/cache_layers", "serving/weights_bytes",
                 "loop/steps_run", "loop/expected_exit_step",
                 "loop/chosen_exit_step"):
        assert metrics[name]["value"] is not None, name
    assert metrics["serving/cache_layers"]["value"] == 12
    assert metrics["serving/weights_bytes"]["value"] == sum(
        a.nbytes for a in jax.tree.leaves(eng.served_weights()))
    assert reg.counter("loop/steps_run").value - steps_run == \
        4 * (reg.counter("serving/ticks").value - ticks)
    assert 1.0 <= metrics["loop/chosen_exit_step"]["value"] <= 4.0
    # the device scopes are in the lowered tick, the old vocabulary too
    fn, avals = eng._program_args[eng.compiled_sites[0]]
    text = fn.lower(*avals).as_text(debug_info=True)
    for scope in ("loop/exit", "blk/qkv", "blk/attn", "blk/kv_scatter",
                  "blk/attn_out", "blk/ffn", "tick/embed", "tick/head",
                  "tick/sample"):
        assert scope in text, scope


def _stripped(hlo: str) -> str:
    """Optimized HLO text without what only names things (as
    tests/test_program_names.py strips it)."""
    hlo = re.sub(r', metadata=\{[^{}]*("[^"]*"[^{}]*)*\}', "", hlo)
    hlo = "\n".join(
        ln for ln in hlo.splitlines() if not re.match(
            r'^(\d+ ["{].*|FileNames|FunctionNames|FileLocations|'
            r'StackFrames)$', ln))
    seen = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: seen.setdefault(m.group(0), f"%n{len(seen)}"),
                  hlo)


def test_the_profiler_leaves_the_looped_tick_as_it_is():
    """On or off, the compiled tick is the same but for metadata."""
    def compiled_text():
        eng, _ = _serve(_net(4), _prompts((5,)), new=2)
        fn, avals = eng._program_args[eng.compiled_sites[0]]
        return _stripped(fn.lower(*avals).compile().as_text())

    off = compiled_text()
    profiler.enable()
    try:
        on = compiled_text()
    finally:
        profiler.disable()
    assert on == off


def _lazy_net(seed, dtype=None):
    paddle.seed(seed)
    with paddle.LazyGuard():
        net = GPT(_config())
    net.eval()
    if dtype:
        net.to(dtype=dtype)
    return net


def test_a_lazy_models_weights_are_drawn_once_into_the_engines_stacks():
    """A model built under ``paddle.LazyGuard`` holds no weights and keeps
    none: its served state is drawn by one seeded call, each parameter
    from its own initializer in the model's type, a layer a row of the
    stacks, and the engine holds the one copy."""
    from paddle_tpu.framework.lazy import is_abstract

    net = _lazy_net(3, "bfloat16")
    eng, _ = _serve(net, _prompts((5, 9)))
    stacked, other = eng.served_weights()
    assert all(is_abstract(p) for p in net.parameters())
    assert net._decode_state()[0] is stacked        # drawn once
    cfg = net.config
    assert registry().gauge("serving/weights_bytes").value \
        == 2 * cfg.num_params()
    eager = _net().bfloat16()._decode_state()
    for mine, theirs in zip(jax.tree.leaves((stacked, other)),
                            jax.tree.leaves(eager)):
        assert mine.shape == theirs.shape and mine.dtype == jnp.bfloat16
    w = np.asarray(stacked["attn.qkv_proj.weight"], np.float32)
    assert w.std() == pytest.approx(cfg.initializer_range, rel=0.05)
    assert not np.array_equal(w[0], w[1])           # a key a layer
    # the output projections' scaled initializer, as the eager model's
    assert np.asarray(stacked["attn.out_proj.weight"], np.float32).std() \
        == pytest.approx(float(np.asarray(
            eager[0]["attn.out_proj.weight"], np.float32).std()), rel=0.1)
    assert np.all(np.asarray(stacked["ln_1.weight"], np.float32) == 1.0)
    assert np.all(np.asarray(other["exit_gate.bias"], np.float32) == 0.0)
    again = _lazy_net(3, "bfloat16")._decode_state()
    other_seed = _lazy_net(4, "bfloat16")._decode_state()
    for k, v in stacked.items():
        assert np.array_equal(v, again[0][k]), k
    assert not np.array_equal(other["lm_head.weight"],
                              other_seed[1]["lm_head.weight"])


def test_a_lazy_model_serves_what_the_reference_computes():
    """The engine on drawn weights (float32, so the limits are the eager
    tests') against the reference on the same weights."""
    net = _lazy_net(7)
    prompts = _prompts((5, 9, 14))
    eng, rids = _serve(net, prompts)
    worst, exit_gap, chosen_same = _judge(net, eng, rids, prompts)
    assert worst <= 1e-4 and exit_gap <= 1e-4 and chosen_same


def test_what_a_looped_model_is_refused():
    """Training the loop and speculative decoding are not this model's yet,
    and say so by what they lack; experts in the tick and QK-norm stay
    refused for serving."""
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer

    net = _net(2)
    with pytest.raises(NotImplementedError, match="looped stack"):
        net.loss(paddle.to_tensor(np.stack(_prompts((8,)))))
    with pytest.raises(NotImplementedError, match="loop_steps"):
        HybridPipelineTrainer(net, None, n_micro=1)
    paddle.seed(1)
    draft = GPT(GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=1,
                          num_heads=2, max_seq_len=64))
    with pytest.raises(NotImplementedError, match="looped model"):
        ServingEngine(net, ServingConfig(
            num_slots=2, page_size=4, pages_per_slot=8,
            spec=SpecConfig(draft_model=draft, k=2)))
    with pytest.raises(NotImplementedError, match="GPT-3's block"):
        ServingEngine(draft, ServingConfig(
            num_slots=2, page_size=4, pages_per_slot=8,
            spec=SpecConfig(draft_model=_net(1), k=2)))
    with pytest.raises(NotImplementedError, match="QK-norm"):
        _net(1, qk_norm=True)._decode_state()
    with pytest.raises(NotImplementedError, match="an expert layer under blk/ffn"):
        GPT(dataclasses.replace(GPTConfig.olmoe_1b_7b(), num_layers=1,
                                hidden_size=64, num_heads=4, vocab_size=128,
                                ffn_hidden_size=64, moe_num_experts=4,
                                moe_top_k=2, moe_expert_width=32,
                                qk_norm=False))._decode_state()


@pytest.mark.parametrize("kind", ["rmsnorm", "rope", "nobias", "swiglu",
                                  "untied", "sandwich"])
def test_each_block_kind_is_served_as_it_is_trained(kind):
    """One architecture field at a time away from GPT-3's block: the
    engine's tokens are the eager forward's argmax."""
    field = {"rmsnorm": dict(norm="rmsnorm"), "rope": dict(position="rope"),
             "nobias": dict(bias=False), "swiglu": dict(ffn="swiglu"),
             "untied": dict(tie_word_embeddings=False),
             "sandwich": dict(sandwich_norm=True)}[kind]
    paddle.seed(2)
    net = GPT(GPTConfig(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                        num_heads=HEADS, max_seq_len=64, **field))
    net.eval()
    prompts = _prompts((5, 11))
    eng, rids = _serve(net, prompts)
    for rid, prompt in zip(rids, prompts):
        out = np.asarray(eng.tokens_so_far(rid), np.int32)
        seq = np.concatenate([prompt, out[:-1]])[None]
        lg = np.asarray(net(paddle.to_tensor(seq))._value)[0]
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        short = lg[at].max(-1) - lg[at, out]
        assert short.max() < 1e-3, (kind, short)


def test_a_handoff_carries_every_cache_layer():
    """The disaggregated handoff exports and imports pages through
    ``Pools.arrays()``: a page spans the leading axis whole, so a looped
    model's 12 cache layers travel with it."""
    net = _net(4)
    (prompt,) = _prompts((9,))
    cfg = dict(num_slots=2, page_size=4, pages_per_slot=8, prefill_chunk=8)
    src = ServingEngine(net, ServingConfig(**cfg))
    dst = ServingEngine(net, ServingConfig(**cfg))
    rid = src.submit(prompt, 6, hold_after_prefill=True)
    while rid not in src._held_ready:
        src.step()
        src.drain(0)
    payload = src.export_held(rid)
    assert payload["k"].shape[0] == payload["v"].shape[0] == 12
    got = dst.admit_prefilled(payload)
    dst.run()
    whole, (r,) = _serve(net, [prompt])
    assert dst.tokens_so_far(got) == whole.tokens_so_far(r)
