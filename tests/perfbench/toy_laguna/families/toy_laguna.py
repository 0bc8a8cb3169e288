"""``families/laguna_serve.py`` at a toy's widths, for the CPU tests: the same
model, engine and loop, with the toy's own tables of the widths its file
must carry (the shipped family holds a file to the published ones) and a
prompt chunk of two of its pages."""
import functools

from perfbench import loader

_real = loader.load_module("families", "laguna_serve")
_ROPE = {
    "full_attention": {
        "rope_theta": 100.0, "rope_type": "yarn", "factor": 8,
        "original_max_position_embeddings": 16, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.2079441541679836,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 50.0,
                          "partial_rotary_factor": 1}}
#: the toy's "published" widths: 4 and 6 query heads over 2 key/value heads
#: of 16, a window of 6, 8 experts of which a token takes 3
PUBLISHED = {
    "hidden_size": 32, "intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "num_experts_per_tok": 3,
    "moe_routed_scaling_factor": 2.5, "sliding_window": 6,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 256,
    "rope_parameters": _ROPE,
    "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7, "mlp_only_layers": [0],
    "num_attention_heads_per_layer": [4, 6, 6, 6] * 2}
TABLES = {"published": PUBLISHED,
          "cut": {"num_hidden_layers": 8, "num_experts": 8,
                  "vocab_size": 192},
          "floors": {"num_hidden_layers": 2, "num_experts": 2,
                     "vocab_size": 24}}

check_widths = functools.partial(_real.check_widths, **TABLES)
model_config = functools.partial(_real.model_config, **TABLES)
build = functools.partial(_real.build, prefill_chunk=8, **TABLES)
limits = _real.limits

run = functools.partial(_real.run, build=build)
