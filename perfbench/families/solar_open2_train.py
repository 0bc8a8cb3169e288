"""Solar-Open2 trained by ``HybridPipelineTrainer``: the model is
``paddle_tpu.models.solar_open2.SolarOpen2``, its sizes from the
configuration file under the keys of the model's ``config.json``. The file
counts the experts held here under ``n_routed_experts`` and the router's
width under ``published``; the model is told both, and the two deviations
of ``seeded_init`` that the cell's seeded weights are drawn with (the file's
``assumed`` says why they are not the library's defaults). A program without that
module (the parent of the PR that brought it) fails in ``build`` at once,
before any weight is made.
"""
from __future__ import annotations

import functools

from perfbench import train_loop

#: layers a period: one softmax layer, then ``gqa_interval`` linear ones
PERIOD = 4


def softmax_layers(c: dict) -> list:
    """``gqa_layers`` is the published list, whole; the layers of it that
    the depth held here has."""
    return [i for i in c["gqa_layers"] if i < c["num_hidden_layers"]]


def check_widths(c: dict) -> None:
    """Solar-Open2's own: the heads' total width is twice the hidden size,
    in the softmax layers and in the linear ones alike; whole periods;
    and the cut keeps to the floors (8 experts held, an eighth of the
    vocabulary)."""
    lin = c["linear_attn_config"]
    wide = c["num_attention_heads"] * c["head_dim"]
    if wide != 2 * c["hidden_size"]:
        raise ValueError(
            f"num_attention_heads {c['num_attention_heads']} x head_dim "
            f"{c['head_dim']} is not twice hidden_size {c['hidden_size']}")
    if c["num_attention_heads"] % c["num_key_value_heads"]:
        raise ValueError(
            f"num_key_value_heads {c['num_key_value_heads']} does not "
            f"divide num_attention_heads {c['num_attention_heads']}")
    if lin["num_heads"] * lin["head_dim"] != wide:
        raise ValueError(
            f"linear_attn_config num_heads {lin['num_heads']} x head_dim "
            f"{lin['head_dim']} is not num_attention_heads x head_dim "
            f"{wide}")
    period = c["gqa_interval"] + 1
    layers = c["num_hidden_layers"]
    if period != PERIOD or layers % period \
            or softmax_layers(c) != list(range(0, layers, period)):
        raise ValueError(
            f"num_hidden_layers {layers} is not whole periods of "
            f"gqa_interval {c['gqa_interval']} + 1 with gqa_layers "
            f"{c['gqa_layers']} every fourth")
    published = c["published"]
    if not 8 <= c["n_routed_experts"] <= published["n_routed_experts"]:
        raise ValueError(
            f"n_routed_experts {c['n_routed_experts']} held is not between "
            f"8 and the published {published['n_routed_experts']}")
    if not published["vocab_size"] <= 8 * c["vocab_size"] \
            <= 8 * published["vocab_size"]:
        raise ValueError(
            f"vocab_size {c['vocab_size']} is under an eighth of the "
            f"published {published['vocab_size']}, or over it")


def model_config(c: dict):
    from paddle_tpu.models.solar_open2 import SolarOpen2Config

    if c["use_rope"] or not c["use_gqa_gate"] or c["kda_use_full_proj"] \
            or not c["kda_allow_neg_eigval"] or not c["norm_topk_prob"] \
            or c["routed_scaling_factor"] != 1 or c["first_k_dense_replace"] \
            or c["tie_word_embeddings"]:
        raise ValueError(
            "the family runs no rotary embedding, a gated softmax layer, "
            "low-rank decay and gate projections, beta in [0, 2], "
            "renormalised routing weights unscaled, experts in every "
            "layer and an untied head")
    lin = c["linear_attn_config"]
    return SolarOpen2Config(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_hidden_layers=c["num_hidden_layers"],
        num_attention_heads=c["num_attention_heads"],
        num_key_value_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], linear_attn_num_heads=lin["num_heads"],
        linear_attn_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        kda_proj_rank=c["assumed_sizes"]["kda_proj_rank"],
        gqa_interval=c["gqa_interval"],
        moe_intermediate_size=c["moe_intermediate_size"],
        n_routed_experts=c["published"]["n_routed_experts"],
        n_shared_experts=c["n_shared_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        rms_norm_eps=c["rms_norm_eps"],
        max_position_embeddings=c["max_position_embeddings"],
        embedding_range=c["seeded_init"]["embedding_range"],
        select_bias_range=c["seeded_init"]["select_bias_range"],
        experts_held=(c["experts_held_first"], c["n_routed_experts"]))


def build(ctx, n_micro: int):
    import paddle_tpu as paddle
    from paddle_tpu.models.solar_open2 import SolarOpen2

    cfg = model_config(ctx.config)
    paddle.seed(ctx.seed31)
    return train_loop.hybrid_trainer(ctx, SolarOpen2(cfg), n_micro)


def limits(c: dict) -> dict:
    return {"vocab_size": c["vocab_size"],
            "max_seq_len": c["max_position_embeddings"]}


def facts_after(ctx, tr) -> dict:
    """What the window's last step routed, outputs of that step: the
    fullest held expert's rows over the mean held expert's, averaged over
    the step's expert-layer calls, and the rows the held experts were
    given in all."""
    import jax

    routed = jax.device_get(tr.aux_stats)
    return {"moe_expert_load_max_over_mean": float(
        routed["moe/load_max"] * ctx.config["n_routed_experts"]
        / routed["moe/assigned"]),
        "moe_rows_held": float(routed["moe/assigned"])}


run = functools.partial(train_loop.run, build=build, limits=limits,
                        facts_after=facts_after)
