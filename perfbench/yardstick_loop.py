"""What one serving tick of a looped language model must move and multiply,
from the configuration file alone (the keys of HF's ``config.json`` and the
``engine`` sizes): nothing here imports the program.

A tick runs the ``L`` shared layers ``T`` times over every token in flight.
The least it can read from HBM is the block weights once a loop step (they
are far larger than any on-chip memory: 4.9 GB a pass), the head once, the
rows of the embedding it looks up, and the keys and values of the live
positions of every (step, layer) cache; what it writes (a token's K and V a
cache layer) is counted too. The exit gate and the norms are left out (4,097
and 8,192 parameters a layer).
"""
from __future__ import annotations

BYTES = 2          # bf16 weights and cache


def layer_matrix_params(c: dict) -> int:
    """Parameters of one layer's seven projections."""
    h = c["hidden_size"]
    return 4 * h * h + 3 * h * c["intermediate_size"]


def tick_bytes(c: dict, live_positions: float, tokens: float) -> float:
    """Bytes one tick must read and write: ``live_positions`` cache
    positions held by the requests in the tick, ``tokens`` tokens in
    flight (decode rows and a chunk's)."""
    steps, layers = c["total_ut_steps"], c["num_hidden_layers"]
    h = c["hidden_size"]
    weights = steps * layers * layer_matrix_params(c) * BYTES
    head = h * c["vocab_size"] * BYTES
    kv_token = 2 * c["num_key_value_heads"] * c["head_dim"] * BYTES
    cache = steps * layers * kv_token * (live_positions + tokens)
    return weights + head + tokens * h * BYTES + cache


def tick_flops(c: dict, live_positions: float, tokens: float,
               sampled: float) -> float:
    """Operations one tick needs: 2 a parameter multiplied a token (the
    blocks ``T`` times; the head for the ``sampled`` rows alone) and the
    attention's scores and weighted sum over the positions each token
    attends to (``live_positions`` is their sum over the tick's tokens)."""
    steps, layers = c["total_ut_steps"], c["num_hidden_layers"]
    blocks = 2.0 * steps * layers * layer_matrix_params(c) * tokens
    head = 2.0 * c["hidden_size"] * c["vocab_size"] * sampled
    attn = 4.0 * steps * layers * c["num_attention_heads"] \
        * c["head_dim"] * live_positions
    return blocks + head + attn


def hbm_roofline_pct(tick_ms: float, c: dict, live_positions: float,
                     tokens: float, hbm_bytes_per_s: float) -> float:
    """The least time the chip could take to move a tick's bytes, over the
    tick's time."""
    least_ms = tick_bytes(c, live_positions, tokens) / hbm_bytes_per_s * 1e3
    return 100.0 * least_ms / tick_ms


def mfu_pct(tick_ms: float, c: dict, live_positions: float, tokens: float,
            sampled: float, bf16_flops: float) -> float:
    return 100.0 * tick_flops(c, live_positions, tokens, sampled) \
        / (tick_ms * 1e-3) / bf16_flops
