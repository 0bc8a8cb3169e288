"""What one serving tick of Ling-3.0-flash's cut must move and multiply, from
the configuration file (the keys of HF's ``config.json``) and the ticks' own
counts of themselves (``models/ling3.TICK_STATS``, means over the run):
the benchmark's own arithmetic, which imports none of the program's and
reads the same work whatever implements it. Bytes and products are the
**unpadded** ones. The cell's trace helper hands these out part by part
(``_ling3_trace.least_ms``: the floor of ``state.step_hbm_roofline_pct`` in
this cell) and whole (``tick_needs``).

``step``       a live row a KDA layer: the state ``32 x 128 x 128`` float32
               read and written once (2 x 2.10 MB), the row's q, k, v, its
               decay a channel and its beta in, o out; about 7 products a
               state entry (decay, ``k^T S``, the rank-one update, ``S^T
               q``). HBM binds it.
``chunk``      a chunk token a KDA layer and head, in the chunked form at
               chunks of 64 (``yardstick_gdn``'s count at these widths);
               the row's state read and written once.
``mla``        the one MLA layer's two calls (decode rows, chunk rows), by
               ``yardstick_mla_dense.call_ops_bytes``: the live latents read
               once, 1,152 B each, and the lesser of the absorbed and the
               expanded form's operations.
experts        of the 128 held experts a layer only those a row was routed
               to are read (**touched**, as the ticks count them), once,
               and multiply their rows.

The rest of a tick: every other matrix is read once (the KDA and MLA
mixers, the dense FFN, routers and shared experts, the head for the sampled
rows) and multiplied by every token.
"""
from __future__ import annotations

from perfbench.yardstick_mla import BYTES, least_ms     # noqa: F401
from perfbench.yardstick_mla_dense import call_ops_bytes, latent_row_bytes

STATE_BYTES = 4     # a float32 state entry
CHUNK = 64          # tokens a chunk of the chunked form


def kinds(c: dict) -> list:
    return ["mla" if (i + 1) % c["layer_group_size"] == 0 else "kda"
            for i in c["layers_held"]]


def kda_layers(c: dict) -> int:
    return kinds(c).count("kda")


def mla_layers(c: dict) -> int:
    return kinds(c).count("mla")


def moe_layers(c: dict) -> int:
    return sum(i >= c["first_k_dense_replace"] for i in c["layers_held"])


def kda_mixer_params(c: dict) -> int:
    """q, k, v, the decay, the output gate and the way out, beta, the taps:
    63.0 M as published."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    kw = nh * c["head_dim"]
    return h * 3 * kw + 3 * h * kw + h * nh \
        + c["short_conv_kernel_size"] * 3 * kw + nh + kw + c["head_dim"]


def mla_mixer_params(c: dict) -> int:
    """One query product, the latent's way down and up, a head's gate and
    the way out: 32.0 M as published."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    return h * nh * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) \
        + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + c["kv_lora_rank"] * nh * (c["qk_nope_head_dim"]
                                    + c["v_head_dim"]) \
        + h * nh + nh * c["v_head_dim"] * h


def expert_params(c: dict) -> int:
    """One routed expert: 5.9 M."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_params(c: dict) -> int:
    """Every matrix a token passes but the routed experts and the head: the
    mixers, the leading dense FFN, routers and shared experts."""
    h = c["hidden_size"]
    routed = c["published"]["num_experts"]
    return kda_layers(c) * kda_mixer_params(c) \
        + mla_layers(c) * mla_mixer_params(c) \
        + (c["num_hidden_layers"] - moe_layers(c)) * 3 * h \
        * c["intermediate_size"] \
        + moe_layers(c) * (h * routed + 3 * h
                           * c["moe_shared_expert_intermediate_size"])


def held_params(c: dict) -> int:
    """The held experts of every expert layer: 755.0 M a layer."""
    return moe_layers(c) * c["num_experts"] * expert_params(c)


def total_params(c: dict) -> int:
    """5.23 G at the cut of ISSUE 49 (norms left out)."""
    return dense_params(c) + held_params(c) \
        + 2 * c["vocab_size"] * c["hidden_size"]


def state_entries(c: dict) -> int:
    return c["num_attention_heads"] * c["head_dim"] ** 2


def step_bytes(c: dict, live: float) -> float:
    """All KDA layers' decode step of ``live`` rows."""
    nh, d = c["num_attention_heads"], c["head_dim"]
    row = 2 * state_entries(c) * STATE_BYTES + 3 * nh * d * BYTES \
        + nh * d * 4 + nh * 4 + nh * d * 4      # q k v, g, beta, o
    return kda_layers(c) * live * row


def step_flops(c: dict, live: float) -> float:
    return kda_layers(c) * live * 7.0 * state_entries(c)


def chunk_bytes(c: dict, tokens: float, rows: float) -> float:
    nh, d = c["num_attention_heads"], c["head_dim"]
    token = 3 * nh * d * BYTES + 2 * nh * d * 4 + nh * 4
    return kda_layers(c) * (
        tokens * token + rows * 2 * state_entries(c) * STATE_BYTES)


def chunk_flops(c: dict, tokens: float) -> float:
    nh, d = c["num_attention_heads"], c["head_dim"]
    return kda_layers(c) * tokens * nh * (3 * 2 * d * d + 4 * CHUNK * d)


def attention_ops_bytes(c: dict, calls) -> tuple:
    """The MLA layers' calls, ``calls`` the ``(pairs, keys)`` of each call of
    one layer: ``(operations, bytes)``."""
    ops = moved = 0.0
    for pairs, keys in calls:
        o, b = call_ops_bytes(c, pairs, keys)
        ops, moved = ops + o, moved + b
    return mla_layers(c) * ops, mla_layers(c) * moved


def attention_least_ms(c: dict, calls, peak) -> float:
    return mla_layers(c) * sum(
        least_ms(*call_ops_bytes(c, pairs, keys), peak)
        for pairs, keys in calls)


def experts_bytes(c: dict, touched_share: float) -> float:
    """The matrices of the held experts that were given a row, once."""
    return touched_share * held_params(c) * BYTES


def tick_bytes(c: dict, s: dict) -> float:
    """What one tick must move: every dense weight and the head once, the
    touched experts once, the embedding's rows, the live rows' states both
    ways, the chunk rows' state and operands, the latents its attention
    reads and the rows and histories it writes."""
    h, nh, d = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    tokens = s["live"] + s["chunk"]
    written = tokens * (mla_layers(c) * latent_row_bytes(c)
                        + kda_layers(c) * 3 * nh * d * BYTES)
    head = h * c["vocab_size"] * BYTES if s["sampled"] else 0.0
    return dense_params(c) * BYTES + experts_bytes(c, s["touched"]) + head \
        + tokens * h * BYTES + step_bytes(c, s["live"]) \
        + chunk_bytes(c, s["chunk"], s["chunk_rows"]) \
        + attention_ops_bytes(c, (s["decode"], s["chunk_attn"]))[1] \
        + written


def tick_flops(c: dict, s: dict) -> float:
    """2 operations a parameter multiplied a token (the dense matrices for
    every token, an expert for each of the rows the held experts were given
    a layer, the head for the sampled rows), the two delta-rule forms and
    the attention's lesser form."""
    tokens = s["live"] + s["chunk"]
    return 2.0 * dense_params(c) * tokens \
        + 2.0 * expert_params(c) * s["expert_rows"] * moe_layers(c) \
        + 2.0 * c["hidden_size"] * c["vocab_size"] * s["sampled"] \
        + step_flops(c, s["live"]) + chunk_flops(c, s["chunk"]) \
        + attention_ops_bytes(c, (s["decode"], s["chunk_attn"]))[0]
