"""What Falcon-H1's tick must move and multiply, from the configuration file
and the window's mean tick shape: the benchmark's own arithmetic, which
imports none of the program's (``yardstick.py``'s peaks are the readers') and
reads the same work whatever implements it. Bytes and products are the
**unpadded** ones: a state is ``heads x P x N`` float32 however a kernel lays
it out, and a token's K and V are 4 heads of 128 however many head rows a
pool carries.

A tick's shape is what its ticks counted (``models/falcon_h1.TICK_STATS``,
means over the run): ``live`` decode rows that moved a state, ``chunk``
tokens of prompt in ``chunk_rows`` rows, and for one layer the ``keys`` its
decode rows and its chunk rows read and the chunk rows' visible query-key
``pairs``. Every layer has both mixers. The cell's trace helper hands these
out part by part (``_falcon_h1_trace.least_ms``: the floors of
``state.step_hbm_roofline_pct``, ``state.chunk_roofline_pct`` and
``attn.full_roofline_pct`` in this cell) and whole (``tick_needs``).

``step``       a live row a layer: the state read and written once (2 x 4.19
               MB), the row's x, B, C, dt in and y out; 5 operations a state
               entry (decay, outer product and sum, the read against C and
               its sum). HBM binds it.
``chunk``      a chunk token a layer, in the chunked form at blocks of
               ``mamba_chunk_size``: a group's scores against half a block
               (Q N), a head's use of them (Q P) and its two products against
               the state (2 x 2 N P); the row's state read and written once.
``attn``       a layer: K and V of the rows' live keys read once (4 heads),
               4 d operations a visible pair and query head (20 heads).
"""
from __future__ import annotations

BYTES = 2           # a bf16 weight, activation or cached K/V entry
STATE_BYTES = 4     # a float32 state entry


def _ssd(c: dict):
    return (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_n_groups"])


def conv_width(c: dict) -> int:
    """``[x | B | C]``."""
    return c["mamba_d_ssm"] + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def ssd_mixer_params(c: dict) -> int:
    """The projection in ``[z | x | B | C | dt]`` and out, the taps and their
    bias, A_log, dt_bias, D and the gated norm: 68.35 M as published."""
    h, heads = c["hidden_size"], c["mamba_n_heads"]
    width = c["mamba_d_ssm"] + conv_width(c) + heads
    return h * width + c["mamba_d_ssm"] * h \
        + (c["mamba_d_conv"] + 1) * conv_width(c) + 3 * heads \
        + c["mamba_d_ssm"]


def attention_params(c: dict) -> int:
    """q, k, v and the way out: 31.46 M as published."""
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return c["hidden_size"] * (q + 2 * kv) + q * c["hidden_size"]


def ffn_params(c: dict) -> int:
    """The SwiGLU and the block's two norms."""
    return 3 * c["hidden_size"] * c["intermediate_size"] \
        + 2 * c["hidden_size"]


def layer_params(c: dict) -> int:
    """Every layer's parameters: what a tick reads once."""
    return c["num_hidden_layers"] * (
        ssd_mixer_params(c) + attention_params(c) + ffn_params(c))


def total_params(c: dict) -> int:
    return layer_params(c) + 2 * c["vocab_size"] * c["hidden_size"] \
        + c["hidden_size"]


def state_entries(c: dict) -> int:
    heads, p, n, _ = _ssd(c)
    return heads * p * n


def _row_operands(c: dict) -> int:
    """A token's x, B and C in, dt in, y out (float32), a layer."""
    heads, p, n, groups = _ssd(c)
    return (heads * p + 2 * groups * n) * BYTES + heads * 4 + heads * p * 4


def step_bytes(c: dict, live: float) -> float:
    """All layers' decode step of ``live`` rows."""
    return c["num_hidden_layers"] * live * (
        2 * state_entries(c) * STATE_BYTES + _row_operands(c))


def step_flops(c: dict, live: float) -> float:
    return c["num_hidden_layers"] * live * 5.0 * state_entries(c)


def chunk_bytes(c: dict, tokens: float, rows: float) -> float:
    return c["num_hidden_layers"] * (
        tokens * _row_operands(c)
        + rows * 2 * state_entries(c) * STATE_BYTES)


def chunk_flops(c: dict, tokens: float) -> float:
    heads, p, n, groups = _ssd(c)
    q = c["mamba_chunk_size"]
    return c["num_hidden_layers"] * tokens * (
        groups * q * n + heads * (q * p + 4 * n * p))


def attention_bytes(c: dict, keys: float) -> float:
    """All layers' K and V of ``keys`` live positions, read once."""
    return c["num_hidden_layers"] * keys * 2 \
        * c["num_key_value_heads"] * c["head_dim"] * BYTES


def attention_flops(c: dict, pairs: float) -> float:
    return c["num_hidden_layers"] * pairs * 4.0 \
        * c["num_attention_heads"] * c["head_dim"]


def least_ms(flops: float, moved: float, peak) -> float:
    """The slower of multiplying and moving, in milliseconds."""
    return 1e3 * max(flops / peak.bf16_flops, moved / peak.hbm_bytes_per_s)


def tick_bytes(c: dict, shape: dict) -> float:
    """What one tick must move: every layer's weights and the head once, the
    embedding's rows of its tokens, the live rows' states both ways, the
    chunk rows' state and operands, the K and V its attention reads and the
    K, V and histories it writes."""
    h = c["hidden_size"]
    tokens = shape["live"] + shape["chunk"]
    written = tokens * c["num_hidden_layers"] * (
        2 * c["num_key_value_heads"] * c["head_dim"] + conv_width(c)) * BYTES
    return (layer_params(c) + c["vocab_size"] * h + h) * BYTES \
        + tokens * h * BYTES + step_bytes(c, shape["live"]) \
        + chunk_bytes(c, shape["chunk"], shape["chunk_rows"]) \
        + attention_bytes(c, shape["decode_keys"] + shape["chunk_keys"]) \
        + written


def tick_flops(c: dict, shape: dict) -> float:
    """2 operations a parameter multiplied a token (the layers' matrices),
    the head for the sampled rows, the rule in both forms and the visible
    pairs (a decode row's pairs are its keys)."""
    tokens = shape["live"] + shape["chunk"]
    return 2.0 * layer_params(c) * tokens \
        + 2.0 * c["vocab_size"] * c["hidden_size"] * shape["sampled"] \
        + step_flops(c, shape["live"]) + chunk_flops(c, shape["chunk"]) \
        + attention_flops(c, shape["decode_keys"] + shape["chunk_pairs"])
