"""What Olmo-Hybrid's tick must move and multiply, from the configuration file
and the window's mean tick shape: the benchmark's own arithmetic, which
imports none of the program's and reads the same work whatever implements
it. Bytes and products are the **unpadded** ones: a state is ``heads x 96 x
192`` float32 however a kernel lays it out, keys are 96 wide however a
kernel pads them, and 30 heads' K and V however many head rows a pool
carries.

A tick's shape is what its ticks counted (``models/olmo_hybrid.TICK_STATS``,
means over the run): ``live`` decode rows that moved a state, ``chunk``
tokens of prompt, and for one full layer the ``keys`` its decode rows and
its chunk rows read and the chunk rows' visible query-key ``pairs``. The
cell's trace helper hands these out part by part (``_olmoh_trace.least_ms``:
the floors of ``state.step_hbm_roofline_pct``, ``state.chunk_roofline_pct``
and ``attn.full_roofline_pct`` in this cell) and whole (``tick_needs``).

``step``       a live row a linear layer: the state read and written once
               (2 x 2.21 MB), the row's q, k, v in and o out; about 7
               products a state entry. HBM binds it.
``chunk``      a chunk token a linear layer and head, in the chunked form at
               chunks of 64: three products against the state (2 dk dv
               each), the pair matrices and their use over half a chunk (2
               C dk + 2 C dv); the row's state read and written once.
``attn``       a full layer: K and V of the rows' live keys read once, 4 d
               operations a visible pair and head.
"""
from __future__ import annotations

BYTES = 2           # a bf16 weight, activation or cached K/V entry
STATE_BYTES = 4     # a float32 state entry
CHUNK = 64          # tokens a chunk of the chunked form


def kinds(c: dict) -> list:
    return c["layer_types"][:c["num_hidden_layers"]]


def linear_layers(c: dict) -> int:
    return kinds(c).count("linear_attention")


def full_layers(c: dict) -> int:
    return kinds(c).count("full_attention")


def _lin(c: dict):
    return (c["linear_num_value_heads"], c["linear_key_head_dim"],
            c["linear_value_head_dim"])


def linear_mixer_params(c: dict) -> int:
    """q, k, v, the output gate and the way out, a and b, the taps, A_log,
    dt_bias and the gated norm: 88.75 M as published."""
    h = c["hidden_size"]
    heads, dk, dv = _lin(c)
    width = heads * (2 * dk + dv)
    return h * width + 2 * h * heads * dv + 2 * h * heads \
        + c["linear_conv_kernel_dim"] * width + 2 * heads + dv


def full_mixer_params(c: dict) -> int:
    h = c["hidden_size"]
    return 4 * h * h + 2 * h


def ffn_params(c: dict) -> int:
    """The SwiGLU and the block's two norms."""
    return 3 * c["hidden_size"] * c["intermediate_size"] \
        + 2 * c["hidden_size"]


def layer_params(c: dict) -> int:
    """Every layer's parameters: what a tick reads once."""
    return linear_layers(c) * linear_mixer_params(c) \
        + full_layers(c) * full_mixer_params(c) \
        + c["num_hidden_layers"] * ffn_params(c)


def total_params(c: dict) -> int:
    return layer_params(c) + 2 * c["vocab_size"] * c["hidden_size"] \
        + c["hidden_size"]


def state_entries(c: dict) -> int:
    heads, dk, dv = _lin(c)
    return heads * dk * dv


def step_bytes(c: dict, live: float) -> float:
    """All linear layers' decode step of ``live`` rows."""
    heads, dk, dv = _lin(c)
    row = 2 * state_entries(c) * STATE_BYTES \
        + heads * (2 * dk + dv) * BYTES + heads * dv * 4
    return linear_layers(c) * live * row


def step_flops(c: dict, live: float) -> float:
    return linear_layers(c) * live * 7.0 * state_entries(c)


def chunk_bytes(c: dict, tokens: float, rows: float) -> float:
    heads, dk, dv = _lin(c)
    token = heads * (2 * dk + dv) * BYTES + heads * dv * 4 + 2 * heads * 4
    return linear_layers(c) * (
        tokens * token + rows * 2 * state_entries(c) * STATE_BYTES)


def chunk_flops(c: dict, tokens: float) -> float:
    heads, dk, dv = _lin(c)
    return linear_layers(c) * tokens * heads * (
        3 * 2 * dk * dv + 2 * CHUNK * dk + 2 * CHUNK * dv)


def attention_bytes(c: dict, keys: float) -> float:
    """All full layers' K and V of ``keys`` live positions, read once."""
    return full_layers(c) * keys * 2 * c["hidden_size"] * BYTES


def attention_flops(c: dict, pairs: float) -> float:
    return full_layers(c) * pairs * 4.0 * c["hidden_size"]


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
    heads, dk, dv = _lin(c)
    written = tokens * (full_layers(c) * 2 * h
                        + linear_layers(c) * heads * (2 * dk + dv)) * BYTES
    return (layer_params(c) + c["vocab_size"] * h + h) * BYTES \
        + tokens * h * BYTES + step_bytes(c, shape["live"]) \
        + chunk_bytes(c, shape["chunk"], shape["chunk_rows"]) \
        + attention_bytes(c, shape["decode_keys"] + shape["chunk_keys"]) \
        + written


def tick_flops(c: dict, shape: dict) -> float:
    """2 operations a parameter multiplied a token (the layers' matrices),
    the head for the sampled rows, the two delta-rule forms and the full
    layers' visible pairs (a decode row's pairs are its keys)."""
    tokens = shape["live"] + shape["chunk"]
    return 2.0 * layer_params(c) * tokens \
        + 2.0 * c["vocab_size"] * c["hidden_size"] * shape["sampled"] \
        + step_flops(c, shape["live"]) + chunk_flops(c, shape["chunk"]) \
        + attention_flops(c, shape["decode_keys"] + shape["chunk_pairs"])
