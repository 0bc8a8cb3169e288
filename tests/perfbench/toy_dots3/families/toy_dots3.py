"""``families/dots3_serve.py`` at a toy's widths, for the CPU tests: the
same model, engine and loop, with the toy's own table of the widths its
file must carry (the shipped family holds a file to the published ones)."""
import functools

from perfbench import loader

_real = loader.load_module("families", "dots3_serve")
#: the toy's "published" widths: window 5, top-k 8, every boundary within a
#: few dozen tokens
PUBLISHED = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "swa_num_attention_heads": 2,
    "swa_num_key_value_heads": 2, "swa_q_lora_rank": 16,
    "swa_kv_lora_rank": 12, "swa_qk_nope_head_dim": 12,
    "swa_qk_rope_head_dim": 4, "swa_v_head_dim": 8, "sliding_window_size": 5,
    "index_n_heads": 4, "index_head_dim": 8, "index_topk": 8,
    "num_experts_per_tok": 4, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "rope_theta": 80000000,
    "swa_rope_theta": 50000, "rms_norm_eps": 1e-05,
    "routed_scaling_factor": 1}

check_widths = functools.partial(_real.check_widths, published=PUBLISHED)
model_config = functools.partial(_real.model_config, published=PUBLISHED)
build = functools.partial(_real.build, published=PUBLISHED)
limits = _real.limits

run = functools.partial(_real.run, build=build)
