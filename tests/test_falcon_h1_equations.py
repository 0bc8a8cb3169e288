"""Falcon-H1's equations at a small size (``tests/falcon_h1_toy.py``): the
whole stack against the float32 reference ``models/falcon_h1_reference.py``
on seeded weights, every control of the reference, each multiplier moved
alone, the vocabulary slice, the published sizes. The engine's side is
``tests/test_falcon_h1.py``."""
import dataclasses

import numpy as np
import pytest

from falcon_h1_toy import build, layers_of, ref, reference, some_tokens
from paddle_tpu.models.falcon_h1 import FalconH1Config


@pytest.fixture(scope="module")
def net():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return some_tokens()


# --- the sizes -----------------------------------------------------------
def test_the_published_sizes_count_thirty_four_billion_parameters():
    c = FalconH1Config.falcon_h1_34b()
    assert c.conv_width == 5120 and c.proj_width == 9248
    assert c.q_width == 2560 and c.kv_width == 512
    assert round(c.layer_params() / 1e6, 1) == 430.1
    assert round(c.num_params() / 1e9, 2) == 33.64
    stage = FalconH1Config(num_hidden_layers=9, vocab_size=32640)
    assert round(stage.num_params() * 2 / 1e9, 2) == 8.41
    # config.json's rope_theta is an integer past int32
    theta = FalconH1Config(rope_theta=100000000000).rope_theta
    assert isinstance(theta, float) and theta == 1e11
    with pytest.raises(ValueError, match="heads of"):
        FalconH1Config(mamba_d_ssm=4000)
    with pytest.raises(NotImplementedError, match="gated norm"):
        FalconH1Config(mamba_norm_before_gate=True)


def test_the_gates_are_drawn_as_mamba2s_initialiser_draws_them(net):
    p = net._decode_state()[0]["layer0"]
    a = np.exp(np.asarray(p["ssd.A_log.weight"], np.float64))
    assert (a >= 1).all() and (a <= 16).all()
    dt = np.log1p(np.exp(np.asarray(p["ssd.dt_bias.weight"], np.float64)))
    assert (dt > 0.9e-3).all() and (dt < 0.11).all()
    np.testing.assert_array_equal(np.asarray(p["ssd.D.weight"]), 1.0)
    assert np.abs(np.asarray(p["ssd.conv.weight"])).max() <= 0.5
    assert "attn.qkv.weight" in p and "ssd.w_in.weight" in p


# --- the whole stack -------------------------------------------------------
def test_the_whole_stack_is_the_references(net, tokens):
    want = reference(net, tokens[:44])["logits"]
    got = np.asarray(net(tokens[:44]))
    np.testing.assert_allclose(got, want, atol=3e-4, rtol=1e-4)


@pytest.mark.parametrize("control", [c for c in ref.CONTROLS[1:]
                                     if not c.startswith("bf16")])
def test_every_control_moves_the_logits(net, tokens, control):
    want = reference(net, tokens[:44])["logits"]
    wrong = reference(net, tokens[:44], control, ticks=(30, 8))["logits"]
    assert np.abs(wrong - want).max() > 2e-3, control


@pytest.mark.parametrize("control", ["bf16_state", "bf16_step"])
def test_a_bf16_state_or_step_moves_the_state(net, tokens, control):
    right = reference(net, tokens[:44])
    wrong = reference(net, tokens[:44], control)
    err = np.linalg.norm(right["states"][0] - wrong["states"][0]) \
        / np.linalg.norm(right["states"][0])
    assert 5e-4 < err < 0.3


MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "mlp_multipliers:0", "mlp_multipliers:1",
               "ssm_multipliers:0", "ssm_multipliers:1", "ssm_multipliers:2",
               "ssm_multipliers:3", "ssm_multipliers:4")


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_moved_alone_moves_the_logits_as_the_references(
        net, tokens, name):
    """One multiplier at 1.5 times its value, the same weights: the model's
    logits move, and to where the reference's move."""
    key, _, at = name.partition(":")
    value = getattr(net.config, key)
    moved = value * 1.5 if not at else tuple(
        v * 1.5 if j == int(at) else v for j, v in enumerate(value))
    other = build(**{key: moved})
    base = np.asarray(net(tokens[:20]))
    got = np.asarray(other(tokens[:20]))
    assert np.abs(got - base).max() > 1e-3, name
    np.testing.assert_allclose(got, reference(other, tokens[:20])["logits"],
                               atol=3e-4, rtol=1e-4)


def test_a_sliced_vocabulary_is_a_smaller_vocabulary(net, tokens):
    """The served cut keeps a slice of the vocabulary's rows in both
    matrices: ids, logits and sampling are over the slice, and the logits of
    the slice are the whole model's for the same ids."""
    cut = build(vocab_size=48)
    ids = tokens[:24] % 48
    # the cut model with the whole model's weights, sliced by rows
    layers, other = net._decode_state()
    sliced = {"embeddings.wte.weight": other["embeddings.wte.weight"][:48],
              "ln_f.weight": other["ln_f.weight"],
              "lm_head.weight": other["lm_head.weight"][:, :48]}
    config = dataclasses.asdict(cut.config)
    got = ref.forward(layers_of(net), sliced, ids, config)
    want = reference(net, ids)["logits"][:, :48]
    np.testing.assert_allclose(ref.logits(got["state"], sliced, config),
                               want, atol=1e-5)
    assert np.asarray(cut(ids)).shape == (24, 48)
