"""What Laguna's tick must move and multiply, from the configuration file and
the window's mean tick shape: the benchmark's own arithmetic, which imports
none of the program's (``yardstick.py``'s peaks are the readers') and reads
the same work whatever implements it. Bytes and products are the **unpadded**
ones: a token's K and V are 8 heads of 128 a layer however a pool lays them
out, and a windowed layer's query sees at most ``sliding_window`` keys
however many pages a walk fetches.

A tick's shape is what its ticks counted (``models/laguna.TICK_STATS``, means
over the run): ``decode`` rows that carried a token, ``chunk`` tokens of
prompt, and, a layer of each kind, the ``keys`` its decode rows and its chunk
rows read and the chunk rows' visible query-key ``pairs`` (a decode row's
pairs are its keys); then what the live tokens gave the held experts. The
cell's trace helper hands these out part by part (``_laguna_trace.least_ms``)
and whole (``tick_needs``).

``attn``         the full layers: K and V of the rows' live keys read once (8
                 heads), 4 d operations a visible pair and query head (48).
``attn_window``  the windowed layers: the same with at most
                 ``sliding_window`` keys a query and 72 query heads.
``experts``      the matrices of the held experts that were given a row,
                 once (``yardstick_moe``'s way: a handful of rows an expert
                 is bound by the weights' bytes).
"""
from __future__ import annotations

BYTES = 2           # a bf16 weight, activation or cached K/V entry
FULL, SLIDING = "full_attention", "sliding_attention"


def kinds(c: dict) -> list:
    """The served layers' kinds: the file keeps the published lists whole."""
    return c["layer_types"][:c["num_hidden_layers"]]


def heads(c: dict) -> list:
    return c["num_attention_heads_per_layer"][:c["num_hidden_layers"]]


def sparse(c: dict) -> list:
    return [m == "sparse"
            for m in c["mlp_layer_types"][:c["num_hidden_layers"]]]


def layers_of(c: dict, kind: str) -> int:
    return kinds(c).count(kind)


def heads_of(c: dict, kind: str) -> int:
    """A layer of ``kind``'s query heads (one number a kind)."""
    return next(h for h, k in zip(heads(c), kinds(c)) if k == kind)


def attention_params(c: dict, nh: int) -> int:
    """q, k, v, the gate's projection and the way out: 44.2 M at 48 heads,
    63.1 M at 72."""
    h, d = c["hidden_size"], c["head_dim"]
    return h * (2 * nh * d + 2 * c["num_key_value_heads"] * d + nh)


def dense_params(c: dict) -> int:
    """Every matrix a tick reads once whatever its tokens chose: attention,
    the dense SwiGLU, the routers, the shared experts and the norms."""
    h = c["hidden_size"]
    n = 0
    for nh, moe in zip(heads(c), sparse(c)):
        n += attention_params(c, nh) + 2 * h
        n += h * c["published"]["num_experts"] \
            + 3 * h * c["shared_expert_intermediate_size"] if moe \
            else 3 * h * c["intermediate_size"]
    return n


def held_params(c: dict) -> int:
    """The held experts' matrices, all sparse layers."""
    return sum(sparse(c)) * c["experts_held"][1] * 3 * c["hidden_size"] \
        * c["moe_intermediate_size"]


def total_params(c: dict) -> int:
    return dense_params(c) + held_params(c) \
        + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"]


def experts_bytes(c: dict, touched_share: float) -> float:
    """The matrices of the held experts that were given a row, once."""
    return touched_share * held_params(c) * BYTES


def attention_bytes(c: dict, kind: str, keys: float) -> float:
    """``kind``'s layers' K and V of ``keys`` positions, read once."""
    return layers_of(c, kind) * keys * 2 * c["num_key_value_heads"] \
        * c["head_dim"] * BYTES


def attention_flops(c: dict, kind: str, pairs: float) -> float:
    return layers_of(c, kind) * pairs * 4.0 * heads_of(c, kind) \
        * c["head_dim"]


def least_ms(flops: float, moved: float, peak) -> float:
    """The slower of multiplying and moving, in milliseconds."""
    return 1e3 * max(flops / peak.bf16_flops, moved / peak.hbm_bytes_per_s)


def attention_least_ms(c: dict, kind: str, s: dict) -> float:
    """The floor of one kind of attention in the mean tick ``s``."""
    pre = "" if kind == FULL else "window_"
    return least_ms(
        attention_flops(c, kind, s[pre + "decode_keys"]
                        + s[pre + "chunk_pairs"]),
        attention_bytes(c, kind, s[pre + "decode_keys"]
                        + s[pre + "chunk_keys"]), s["peak"])


def tick_bytes(c: dict, s: dict) -> float:
    """What one tick must move: every dense weight and the head once, the
    touched experts once, the embedding's rows of its tokens, the K and V
    both kinds of attention read and the K and V it writes."""
    h = c["hidden_size"]
    tokens = s["decode"] + s["chunk"]
    written = tokens * c["num_hidden_layers"] * 2 \
        * c["num_key_value_heads"] * c["head_dim"] * BYTES
    head = h * c["vocab_size"] * BYTES if s["sampled"] else 0.0
    return dense_params(c) * BYTES + experts_bytes(c, s["touched"]) + head \
        + tokens * h * BYTES + written \
        + attention_bytes(c, FULL, s["decode_keys"] + s["chunk_keys"]) \
        + attention_bytes(c, SLIDING, s["window_decode_keys"]
                          + s["window_chunk_keys"])


def tick_flops(c: dict, s: dict) -> float:
    """2 operations a parameter multiplied a token (the dense matrices for
    every token, an expert for each of the rows the held experts were given
    a layer, the head for the sampled rows) and both kinds of attention's
    visible pairs."""
    h = c["hidden_size"]
    tokens = s["decode"] + s["chunk"]
    return 2.0 * dense_params(c) * tokens \
        + 2.0 * sum(sparse(c)) * s["expert_rows"] * 3 * h \
        * c["moe_intermediate_size"] \
        + 2.0 * s["sampled"] * h * c["vocab_size"] \
        + attention_flops(c, FULL, s["decode_keys"] + s["chunk_pairs"]) \
        + attention_flops(c, SLIDING, s["window_decode_keys"]
                          + s["window_chunk_pairs"])
