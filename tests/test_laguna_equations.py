"""Laguna's equations at a small size (``tests/laguna_toy.py``): the model's
one-pass forward against the float32 reference, each control of the
reference shown to change the result beyond the tolerance the served path
keeps, the gate and the partial rotation by hand, YaRN's table against the
reference's own, and **the shares add up**: four chips' expert parts and the
shared expert once are the uncut layer."""
import dataclasses
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from laguna_toy import build, layers_of, ref, reference, some_tokens
from paddle_tpu.distributed.moe import held_moe
from paddle_tpu.models.deepseek_v2 import rope_by_table
from paddle_tpu.models.laguna import FULL, SLIDING, LagunaConfig

TOL = 3e-4


@pytest.fixture(scope="module")
def net():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return some_tokens()[:33]


@pytest.fixture(scope="module")
def served(net, tokens):
    return np.asarray(net(tokens))


@pytest.fixture(scope="module")
def right(net, tokens):
    return reference(net, tokens)


def test_one_prefill_is_the_references_forward(served, right):
    np.testing.assert_allclose(served, right["logits"], atol=TOL)


def test_a_second_forward_of_a_length_compiles_nothing(net, tokens, served):
    """``LayerwiseLM.forward`` is one jitted program a chunk width, kept on
    the model (a ``jax.jit`` made anew a call would trace and compile
    anew: its cache is keyed on the function it was given)."""
    again = np.asarray(net(tokens[::-1].copy()))
    assert again.shape == served.shape and not np.allclose(again, served)
    (width, tick), = net._forward_ticks.items()
    assert width == 40 and tick._cache_size() == 1


@pytest.mark.parametrize("control", [c for c in ref.CONTROLS if c])
def test_every_control_is_another_model(net, tokens, served, right, control):
    wrong = reference(net, tokens, control=control)["logits"]
    assert np.max(np.abs(wrong - right["logits"])) > 30 * TOL, control
    assert np.max(np.abs(wrong - served)) > 30 * TOL, control


def test_the_reference_in_small_pieces_is_the_reference(net, tokens, right,
                                                        monkeypatch):
    """What lets the check's reference stand beside an engine that fills the
    chip at 17 k positions: blocks of queries that shrink with the context,
    no layer's keys and values kept but those asked for, and a stack that
    ends at the last layer asked for."""
    assert [ref.query_block(per, s) for per, s in (
        (9, 8192), (6, 8192), (9, 17408), (6, 17408))] == [512, 512, 128, 256]
    monkeypatch.setattr(ref, "_SCORES", 3 * 8 * 33)
    assert ref.query_block(3, 33) == 8
    other = net._decode_state()[1]
    config = dataclasses.asdict(net.config)
    got = ref.forward(layers_of(net), other, tokens, config,
                      held=net.config.held, keep=())
    np.testing.assert_allclose(got["state"], right["state"], atol=1e-5)
    assert all(k is None for k in got["keys"] + got["values"])
    first = ref.forward(itertools.islice(layers_of(net), 2), other, tokens,
                        config, held=net.config.held, keep=(0, 1))
    assert len(first["keys"]) == 2
    for mine, theirs in zip(first["keys"] + first["values"],
                            right["keys"][:2] + right["values"][:2]):
        np.testing.assert_allclose(mine, theirs, atol=1e-5)


def test_unknown_controls_are_refused(net, tokens):
    with pytest.raises(ValueError, match="unknown control"):
        reference(net, tokens, control="no_such_thing")


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(net):
    """Experts 0-1, 2-3, 4-5, 6-7 of 8 as four chips hold them: the
    reference's parts without the shared expert plus the shared expert once
    equal the uncut layer, and the served ``held_moe`` gives each part."""
    c = net.config
    config = dataclasses.asdict(c)
    w = list(layers_of(net))[1]
    y = jnp.asarray(np.random.default_rng(3).standard_normal(
        (24, c.hidden_size)), jnp.float32)
    whole, chosen, rows = ref.moe(y, w, config, (0, 8))
    assert rows.sum() == 24 * 3
    cut = lambda lo, n: dict(w, **{                         # noqa: E731
        k: w[k][lo:lo + n] for k in ("ffn.w_gate", "ffn.w_up", "ffn.w_down")})
    parts, mine = [], []
    for lo in range(0, 8, 2):
        part, _, r = ref.moe(y, cut(lo, 2), config, (lo, 2),
                             control="no_shared")
        parts.append(part)
        np.testing.assert_array_equal(r, rows[lo:lo + 2])
        got, r2 = held_moe(
            y, w["ffn.gate"], *(cut(lo, 2)[k] for k in (
                "ffn.w_gate", "ffn.w_up", "ffn.w_down")),
            c.num_experts_per_tok, (lo, 2), scoring="sigmoid",
            routed_scaling=c.moe_routed_scaling_factor)
        np.testing.assert_allclose(got, part, atol=1e-5)
        np.testing.assert_array_equal(r2, r)
        mine.append(got)
    shared = whole - ref.moe(y, w, config, (0, 8), control="no_shared")[0]
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5)
    assert float(jnp.max(jnp.abs(shared))) > 1e-3


def test_the_gate_is_a_number_a_head_on_the_attention_output(net, tokens):
    """With ``W_g`` at zero every gate is a half: the layer's attention adds
    half of what it adds ungated."""
    config = dataclasses.asdict(net.config)
    layers = [dict(w) for w in layers_of(net)]
    other = net._decode_state()[1]
    for w in layers:
        w["attn.gate.weight"] = jnp.zeros_like(w["attn.gate.weight"])
        w["attn.o.weight"] = 2.0 * w["attn.o.weight"]
    halved = ref.forward(iter(layers), other, tokens, config)["state"]
    ungated = reference(net, tokens, control="no_gate")["state"]
    np.testing.assert_allclose(halved, ungated, atol=1e-5)


def test_the_full_layers_turn_half_of_a_head_and_the_sliding_all_of_it():
    c = LagunaConfig.tiny()
    x = jnp.asarray(np.random.default_rng(5).standard_normal((7, 3, 16)),
                    jnp.float32)
    pos = jnp.arange(7, dtype=jnp.int32) + 11
    inv, scale = c.rotary(FULL)
    assert len(inv) == 4 and scale == pytest.approx(0.1 * np.log(8) + 1)
    got = rope_by_table(x, pos, inv, scale)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    assert float(jnp.max(jnp.abs(got[..., :8] - x[..., :8]))) > 0.1
    inv, scale = c.rotary(SLIDING)
    assert len(inv) == 8 and scale == 1.0
    turned = rope_by_table(x, pos, inv, scale)
    assert float(jnp.min(jnp.max(jnp.abs(turned - x), axis=(0, 1)))) > 0.01
    # a rotation keeps a pair's length
    np.testing.assert_allclose(
        jnp.square(turned[..., :8]) + jnp.square(turned[..., 8:]),
        jnp.square(x[..., :8]) + jnp.square(x[..., 8:]), atol=1e-5)


@pytest.mark.parametrize("config", [LagunaConfig.tiny(), LagunaConfig()],
                         ids=["toy", "published"])
def test_yarns_table_is_the_references_own(config):
    want, scale, d = ref.inv_freq(FULL, dataclasses.asdict(config))
    inv, got_scale = config.rotary(FULL)
    assert d == config.head_dim // 2 == 2 * len(inv)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert got_scale == scale
    if config.head_dim == 128:
        assert scale == 1.4852030263919618
        # the fastest pairs keep their frequency, the slowest are / 128
        plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
        np.testing.assert_allclose(inv[:8], plain[:8], rtol=1e-6)
        np.testing.assert_allclose(inv[-1], plain[-1] / 128, rtol=1e-6)


def test_the_published_sizes_are_the_issues_arithmetic():
    c = LagunaConfig(num_hidden_layers=6, experts_held=(0, 64),
                     vocab_size=25088)
    assert c.layer_types[:6] == (FULL, SLIDING, SLIDING, SLIDING, FULL,
                                 SLIDING)
    assert c.num_attention_heads_per_layer == (48, 72, 72, 72, 48, 72)
    assert [round(c.layer_params(i) / 1e6, 1) for i in (0, 1, 4)] \
        == [157.4, 677.3, 658.4]
    assert round(c.num_params() / 1e9, 2) == 3.68
    with pytest.raises(NotImplementedError, match="a gate a head"):
        LagunaConfig(moe_router_logit_softcapping=30.0)
    with pytest.raises(ValueError, match="do not divide"):
        LagunaConfig.tiny(num_attention_heads_per_layer=(4, 6, 6, 4, 5))
