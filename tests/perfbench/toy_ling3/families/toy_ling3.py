"""``families/ling3_serve.py`` at a toy's widths, for the CPU tests: the same
model, engine and loop, with the toy's own table of the widths its file must
carry (the shipped family holds a file to the published ones) and a prompt
chunk of two of its pages."""
import functools

from perfbench import loader

_real = loader.load_module("families", "ling3_serve")
#: the toy's "published" widths: 4 heads of 16 x 16 beside 4 latent heads
PUBLISHED = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "moe_shared_expert_intermediate_size": 16, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "kv_lora_rank": 8, "q_lora_rank": None,
    "qk_head_dim": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "rope_theta": 100, "rope_scaling": None,
    "layer_group_size": 3, "first_k_dense_replace": 2,
    "num_experts_per_tok": 3, "num_shared_experts": 1, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-06,
    "max_position_embeddings": 256}

check_widths = functools.partial(_real.check_widths, published=PUBLISHED)
model_config = functools.partial(_real.model_config, published=PUBLISHED)


def build(ctx):
    """Four slots whose requests come and go: every one is recorded, so that
    whatever still decodes when a run ends has a record for the check."""
    net, eng = _real.build(ctx, published=PUBLISHED, prefill_chunk=8)
    eng.tick_record.watch = lambda rid: True
    return net, eng


limits = _real.limits

run = functools.partial(_real.run, build=build)
