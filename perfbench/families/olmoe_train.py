"""OLMoE trained by ``HybridPipelineTrainer``, as ``families/gpt_train.py``
trains GPT: the model is ``paddle_tpu.models.GPT`` with the block's
architecture fields at OLMoE's values (``GPTConfig.olmoe_1b_7b()``), its
sizes from the configuration file under the keys of HF's ``config.json``.
A program without those fields (the parent of the PR that brought them)
fails in ``build`` at once, before any weight is made.
"""
from __future__ import annotations

import functools

from perfbench import train_loop


def check_widths(c: dict) -> None:
    """OLMoE's own: full multi-head attention over the hidden size, and
    ``ffn_hidden_size`` the width one token passes through."""
    if c["num_attention_heads"] * c["head_dim"] != c["hidden_size"]:
        raise ValueError(
            f"num_attention_heads {c['num_attention_heads']} x head_dim "
            f"{c['head_dim']} is not hidden_size {c['hidden_size']}")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError(
            f"num_key_value_heads {c['num_key_value_heads']} is not "
            f"num_attention_heads {c['num_attention_heads']}")
    if c["num_experts_per_tok"] * c["intermediate_size"] \
            != c["ffn_hidden_size"]:
        raise ValueError(
            f"num_experts_per_tok {c['num_experts_per_tok']} x "
            f"intermediate_size {c['intermediate_size']} is not "
            f"ffn_hidden_size {c['ffn_hidden_size']}")
    if c["num_heads"] != c["num_attention_heads"]:
        raise ValueError(f"num_heads {c['num_heads']} is not "
                         f"num_attention_heads {c['num_attention_heads']}")


def model_config(c: dict):
    from paddle_tpu.models import GPTConfig

    if c["num_key_value_heads"] != c["num_attention_heads"] \
            or c["norm_topk_prob"] or c["hidden_act"] != "silu" \
            or c["attention_bias"]:
        raise ValueError("the family runs full multi-head attention, "
                         "unrenormalised routing weights, SiLU and no bias")
    preset = GPTConfig.olmoe_1b_7b()
    return GPTConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        max_seq_len=c["max_position_embeddings"],
        ffn_hidden_size=c["ffn_hidden_size"],
        layer_norm_eps=c["rms_norm_eps"],
        tie_word_embeddings=c["tie_word_embeddings"],
        norm=preset.norm, position=preset.position,
        rope_theta=float(c["rope_theta"]), qk_norm=preset.qk_norm,
        bias=c["attention_bias"], ffn=preset.ffn,
        moe_num_experts=c["num_experts"],
        moe_top_k=c["num_experts_per_tok"],
        moe_expert_width=c["intermediate_size"], moe_dropless=True,
        moe_aux_weight=c["router_aux_loss_coef"],
        moe_z_weight=c["router_z_loss_coef"])


def build(ctx, n_micro: int):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT

    cfg = model_config(ctx.config)
    paddle.seed(ctx.seed31)
    return train_loop.hybrid_trainer(ctx, GPT(cfg), n_micro)


def limits(c: dict) -> dict:
    return {"vocab_size": c["vocab_size"],
            "max_seq_len": c["max_position_embeddings"]}


def facts_after(ctx, tr) -> dict:
    """What the window's last step routed, outputs of that step: the
    fullest expert's rows over the mean expert's, averaged over the
    step's expert-layer calls."""
    import jax

    routed = jax.device_get(tr.aux_stats)
    return {"moe_expert_load_max_over_mean": float(
        routed["moe/load_max"] * ctx.config["num_experts"]
        / routed["moe/assigned"])}


run = functools.partial(train_loop.run, build=build, limits=limits,
                        facts_after=facts_after)
