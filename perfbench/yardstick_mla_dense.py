"""What one serving tick of DeepSeek-V2's cut must move and multiply, from the
configuration file (the keys of HF's ``config.json``) and the ticks' own
counts of what they attended: nothing here imports the program.

**Dense latent attention** is counted a call at a time (one call a layer for
the tick's decode rows, one for its chunk rows), from two numbers the tick
reports of itself: the visible query-key *pairs* of the call (a live query
at position ``t`` sees ``t + 1`` keys) and the live *keys* of its rows (each
row's live latents, once). The bytes are the keys read once, 1,152 B each.
The operations are the **lesser of the two forms'** of arXiv:2405.04434
section 2.1.3, whatever the program runs:

- absorbed: every head scores a pair over ``kv_lora_rank + rope`` and weighs
  it over ``kv_lora_rank``: ``2 nh (576 + 512)`` a pair;
- expanded: ``k_nope`` and ``v`` are made from the latents first, ``2 * 512 *
  nh * (128 + 128)`` a visible key a call, then ``2 nh (192 + 128)`` a pair.

So a later change of form cannot read over 100 % of the roofline. The least
time of a call is the slower of multiplying and moving. Norms, softmaxes
and the rotation are left out.

The rest of a tick: every matrix a token passes but the routed experts is
read once (attention, the dense FFN, routers, shared experts, the head for
the sampled rows) and multiplied by every token; of the held experts only
those a row was routed to are read, once, and multiply their rows.
"""
from __future__ import annotations

# one routed expert (23.6 M), the expert layers, the slower of multiplying and
# moving: the same counts as the dots3 yardstick's, under the same keys
from perfbench.yardstick_mla import (BYTES, expert_params, least_ms,
                                     moe_layers)


def attention_params(c: dict) -> int:
    """Matrices of one layer's attention: 149.2 M."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    return h * c["q_lora_rank"] \
        + c["q_lora_rank"] * nh * (c["qk_nope_head_dim"]
                                   + c["qk_rope_head_dim"]) \
        + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + c["kv_lora_rank"] * nh * (c["qk_nope_head_dim"] + c["v_head_dim"]) \
        + nh * c["v_head_dim"] * h


def dense_params(c: dict) -> int:
    """Every matrix a token passes but the routed experts and the head:
    attention, the leading dense FFNs, routers and shared experts."""
    routed = c["published"]["n_routed_experts"]
    return c["num_hidden_layers"] * attention_params(c) \
        + c["first_k_dense_replace"] * 3 * c["hidden_size"] \
        * c["intermediate_size"] \
        + moe_layers(c) * (c["hidden_size"] * routed
                           + c["n_shared_experts"] * expert_params(c))


def held_params(c: dict) -> int:
    """The held experts of every expert layer: 471.9 M a layer."""
    return moe_layers(c) * c["n_routed_experts"] * expert_params(c)


def total_params(c: dict) -> int:
    """3.145 B at the cut of ISSUE 40 (norms left out)."""
    return dense_params(c) + held_params(c) \
        + 2 * c["vocab_size"] * c["hidden_size"]


def latent_row_bytes(c: dict) -> int:
    """One token's cached row in one layer: 1,152 B."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * BYTES


def call_ops_bytes(c: dict, pairs: float, keys: float) -> tuple:
    """One layer's call of the dense latent attention: ``pairs`` visible
    query-key pairs over ``keys`` live latents. ``(operations, bytes)``, the
    operations the lesser of the absorbed and the expanded form's."""
    nh, rank, rope = c["num_attention_heads"], c["kv_lora_rank"], \
        c["qk_rope_head_dim"]
    nope, v = c["qk_nope_head_dim"], c["v_head_dim"]
    absorbed = 2.0 * nh * (rank + rope + rank) * pairs
    expanded = 2.0 * nh * (nope + rope + v) * pairs \
        + 2.0 * rank * nh * (nope + v) * keys
    return min(absorbed, expanded), keys * latent_row_bytes(c)


def attention_least_ms(c: dict, calls, peak) -> float:
    """The least time of a tick's dense attention: ``calls`` the ``(pairs,
    keys)`` of each call of one layer, every layer alike."""
    return c["num_hidden_layers"] * sum(
        least_ms(*call_ops_bytes(c, pairs, keys), peak)
        for pairs, keys in calls)


def experts_bytes(c: dict, touched_share: float) -> float:
    """The matrices of the held experts that were given a row, once."""
    return touched_share * held_params(c) * BYTES


def tick_bytes(c: dict, tokens: float, calls, sampled: float,
               touched_share: float) -> float:
    """Bytes one tick must read and write: every dense weight once, the
    head when a row samples, the touched experts once, the embedding's
    rows, the latents its attention reads and the rows it writes."""
    layers = c["num_hidden_layers"]
    read = layers * sum(call_ops_bytes(c, p, k)[1] for p, k in calls)
    head = c["hidden_size"] * c["vocab_size"] * BYTES if sampled else 0.0
    return dense_params(c) * BYTES + experts_bytes(c, touched_share) + head \
        + tokens * c["hidden_size"] * BYTES + read \
        + tokens * layers * latent_row_bytes(c)


def tick_flops(c: dict, tokens: float, calls, sampled: float,
               expert_rows: float) -> float:
    """Operations one tick needs: 2 a parameter multiplied a token (the
    dense matrices for every token, an expert for each of the
    ``expert_rows`` rows the held experts were given a layer, the head for
    the ``sampled`` rows) and the attention's lesser form."""
    attention = c["num_hidden_layers"] * sum(
        call_ops_bytes(c, p, k)[0] for p, k in calls)
    return 2.0 * dense_params(c) * tokens \
        + 2.0 * expert_params(c) * expert_rows * moe_layers(c) \
        + 2.0 * c["hidden_size"] * c["vocab_size"] * sampled + attention
