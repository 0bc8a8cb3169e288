"""``families/deepseek_v2_serve.py`` at a toy's widths, for the CPU tests: the
same model, engine and loop, with the toy's own table of the widths its file
must carry (the shipped family holds a file to the published ones)."""
import functools

from perfbench import loader

_real = loader.load_module("families", "deepseek_v2_serve")
#: the toy's "published" widths: 16 experts in 4 groups of which a token
#: keeps 2, a YaRN block whose original 16 positions every prompt passes
PUBLISHED = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "num_experts_per_tok": 3, "n_shared_experts": 2,
    "n_group": 4, "topk_group": 2, "first_k_dense_replace": 1,
    "rope_theta": 100, "rms_norm_eps": 1e-06, "routed_scaling_factor": 4,
    "max_position_embeddings": 256,
    "rope_scaling": {
        "beta_fast": 4, "beta_slow": 1, "factor": 8, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 16,
        "type": "yarn"}}

check_widths = functools.partial(_real.check_widths, published=PUBLISHED)
model_config = functools.partial(_real.model_config, published=PUBLISHED)
build = functools.partial(_real.build, published=PUBLISHED)
limits = _real.limits

run = functools.partial(_real.run, build=build)
