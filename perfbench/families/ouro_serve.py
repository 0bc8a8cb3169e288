"""Ouro, a looped language model, served by ``ServingEngine`` as
``families/gpt_serve.py`` serves GPT: the model is ``paddle_tpu.models.GPT``
with the block's architecture fields at Ouro's values (RMSNorm, RoPE, no
bias, SwiGLU, sandwich norms, an untied head) and the loop
(``loop_steps``, ``exit_threshold``), its sizes from the configuration file
under the keys of HF's ``config.json``. A program without those fields (the
parent of the PR that brought them) fails in ``model_config`` at once,
before any weight is made.

The model is built under ``paddle.LazyGuard`` and stays abstract: the engine
draws its weights on the chip in one jitted, seeded call, in bf16, straight
into the stacks it serves from. Built eagerly on the chip, the float32 model
alone is 10.7 GB of its 15.75, and the cast and the engine's stacks would
not fit beside it.
"""
from __future__ import annotations

import functools

from perfbench import loader, serve_loop

_gpt = loader.load_module("families", "gpt_serve")
warm_up, limits, device_state = \
    _gpt.warm_up, _gpt.limits, _gpt.device_state


def check_widths(c: dict) -> None:
    """Ouro's own: full multi-head attention over the hidden size, a layer
    type for every layer, and a loop that runs. The loop's steps and the
    depth of the pools are not stated again: the model's ``loop_steps`` is
    ``total_ut_steps`` and the engine keeps a cache for every (loop step,
    layer) of it."""
    if c["num_attention_heads"] * c["head_dim"] != c["hidden_size"]:
        raise ValueError(
            f"hidden_size {c['hidden_size']} is not num_attention_heads "
            f"{c['num_attention_heads']} x head_dim {c['head_dim']}")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError(
            f"num_key_value_heads {c['num_key_value_heads']} is not "
            f"num_attention_heads {c['num_attention_heads']}")
    if len(c["layer_types"]) != c["num_hidden_layers"]:
        raise ValueError(
            f"layer_types names {len(c['layer_types'])} layers, "
            f"num_hidden_layers {c['num_hidden_layers']}")
    if c["total_ut_steps"] < 1:
        raise ValueError(f"total_ut_steps {c['total_ut_steps']} is under 1")


def model_config(c: dict):
    from paddle_tpu.models import GPTConfig

    check_widths(c)
    if c["hidden_act"] != "silu" or c["rope_scaling"] is not None \
            or c["sliding_window"] is not None or c["use_sliding_window"] \
            or set(c["layer_types"]) != {"full_attention"}:
        raise ValueError("the family runs SiLU-gated FFNs, unscaled RoPE "
                         "and full attention in every layer")
    return GPTConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        max_seq_len=c["max_position_embeddings"],
        ffn_hidden_size=c["intermediate_size"],
        layer_norm_eps=c["rms_norm_eps"],
        initializer_range=c["initializer_range"],
        tie_word_embeddings=c["tie_word_embeddings"], norm="rmsnorm",
        position="rope", rope_theta=float(c["rope_theta"]), bias=False,
        ffn="swiglu", sandwich_norm=True, loop_steps=c["total_ut_steps"],
        exit_threshold=float(c["early_exit_threshold"]))


def build(ctx):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT
    from paddle_tpu.serving import ServingConfig, ServingEngine

    c, e = ctx.config, ctx.config["engine"]
    cfg = model_config(c)
    if c["dtype"] != "bfloat16":
        raise ValueError(f"dtype {c['dtype']!r}: this family serves bf16")
    paddle.seed(ctx.seed31)
    with paddle.LazyGuard():
        net = GPT(cfg)
    net.eval()
    net.bfloat16()
    eng = ServingEngine(net, ServingConfig(
        num_slots=e["num_slots"], page_size=e["page_size"],
        pages_per_slot=e["pages_per_slot"], kv_dtype=e["kv_dtype"],
        prefix_cache=e["prefix_cache"], decode=e["decode"]))
    return net, eng


run = functools.partial(serve_loop.run, build=build, warm_up=warm_up,
                        limits=limits, device_state=device_state)
