"""``families/olmo_hybrid_serve.py`` at a toy's widths, for the CPU tests: the
same model, engine and loop, with the toy's own table of the widths its file
must carry (the shipped family holds a file to the published ones) and a
prompt chunk of two of its pages."""
import functools

from perfbench import loader

_real = loader.load_module("families", "olmo_hybrid_serve")
#: the toy's "published" widths: 6 heads of 24 x 48 beside 6 of 8
PUBLISHED = {
    "vocab_size": 96, "hidden_size": 48, "intermediate_size": 64,
    "num_attention_heads": 6, "num_key_value_heads": 6,
    "linear_num_key_heads": 6, "linear_num_value_heads": 6,
    "linear_key_head_dim": 24, "linear_value_head_dim": 48,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 128,
    "rope_parameters": {"rope_theta": None}}

check_widths = functools.partial(_real.check_widths, published=PUBLISHED)
model_config = functools.partial(_real.model_config, published=PUBLISHED)
build = functools.partial(_real.build, published=PUBLISHED, prefill_chunk=8)
limits = _real.limits

run = functools.partial(_real.run, build=build)
