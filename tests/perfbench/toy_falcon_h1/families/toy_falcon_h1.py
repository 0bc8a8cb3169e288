"""``families/falcon_h1_serve.py`` at a toy's widths, for the CPU tests: the
same model, engine and loop, with the toy's own tables of the widths and
multipliers its file must carry (the shipped family holds a file to the
published ones) and a prompt chunk of two of its pages."""
import functools

from perfbench import loader

_real = loader.load_module("families", "falcon_h1_serve")
#: the toy's "published" widths: 4 SSD heads of 8 x 16 in 2 groups beside 4
#: query heads over 2 key/value heads of 16
PUBLISHED = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_d_ssm": 32,
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_norm_before_gate": False,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "max_position_embeddings": 256, "embedding_multiplier": 1.5,
    "lm_head_multiplier": 0.5, "ssm_in_multiplier": 0.8,
    "ssm_out_multiplier": 1.25, "ssm_multipliers": [1.1, 0.9, 0.7, 1.3, 0.6],
    "attention_in_multiplier": 0.9, "attention_out_multiplier": 1.2,
    "key_multiplier": 0.75, "mlp_multipliers": [0.85, 1.15]}
TABLES = {"published": PUBLISHED,
          "cut": {"num_hidden_layers": 6, "vocab_size": 192},
          "floors": {"num_hidden_layers": 2, "vocab_size": 24}}

check_widths = functools.partial(_real.check_widths, **TABLES)
model_config = functools.partial(_real.model_config, **TABLES)
build = functools.partial(_real.build, prefill_chunk=8, **TABLES)
limits = _real.limits

run = functools.partial(_real.run, build=build)
