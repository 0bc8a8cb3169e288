"""What one serving tick of a latent-attention model with a sparse indexer,
windowed layers and a held share of its experts must move and multiply, from
the configuration file alone (the keys of HF's ``config.json`` and the
``engine`` sizes): nothing here imports the program.

It counts the *needed* work, whatever implements it: a row's live indexer
keys are read once and every query scores all it may see; a query attends
``index_topk`` latents (fewer while fewer are visible) and fetches them
itself, for no two queries share a selection; a windowed row reads its
window's latents once; of the held experts only those a row was routed to
have their matrices read, once; every other weight is read once a tick.
Norms, gates' sigmoids, softmaxes and the top-k itself are left out.

A tick's shape is ``decode`` rows of one query and ``chunks`` rows of
``chunk`` queries, each row with ``context`` positions behind it (the mean
the live requests held: a decode row's is longer and a chunk row's shorter
than that, by about as much).
"""
from __future__ import annotations

BYTES = 2          # bf16 weights and caches
FULL, SLIDING = "full_attention", "sliding_attention"


def _w(c: dict, kind: str) -> dict:
    pre = "swa_" if kind == SLIDING else ""
    return {k: c[pre + name] for k, name in (
        ("heads", "num_attention_heads"), ("q_rank", "q_lora_rank"),
        ("kv_rank", "kv_lora_rank"), ("nope", "qk_nope_head_dim"),
        ("rope", "qk_rope_head_dim"), ("v", "v_head_dim"))}


def layers_of(c: dict, kind: str) -> int:
    return sum(k == kind for k in c["layer_types"])


def moe_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def attention_params(c: dict, kind: str) -> int:
    """Matrices of one layer's attention: 144.0 M in a full layer (the
    indexer's 9.37 M among them), 90.8 M in a sliding one."""
    h, w = c["hidden_size"], _w(c, kind)
    n = h * w["q_rank"] + w["q_rank"] * w["heads"] * (w["nope"] + w["rope"]) \
        + h * (w["kv_rank"] + w["rope"]) \
        + w["kv_rank"] * w["heads"] * (w["nope"] + w["v"]) \
        + w["heads"] * w["v"] * h + h * w["heads"]
    if kind == FULL:
        n += w["q_rank"] * c["index_n_heads"] * c["index_head_dim"] \
            + h * c["index_head_dim"] + h * c["index_n_heads"]
    return n


def expert_params(c: dict) -> int:
    """One routed (or the shared) expert: 23.6 M."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_params(c: dict) -> int:
    """Every matrix a token passes but the routed experts and the head:
    attention, the leading dense FFNs, routers and shared experts."""
    routed = c["published"]["n_routed_experts"]
    return layers_of(c, FULL) * attention_params(c, FULL) \
        + layers_of(c, SLIDING) * attention_params(c, SLIDING) \
        + c["first_k_dense_replace"] * 3 * c["hidden_size"] \
        * c["intermediate_size"] \
        + moe_layers(c) * (c["hidden_size"] * routed + expert_params(c))


def held_params(c: dict) -> int:
    """The held experts of every expert layer: 755.0 M a layer."""
    return moe_layers(c) * c["n_routed_experts"] * expert_params(c)


def total_params(c: dict) -> int:
    """4.087 B at the cut of ISSUE 37 (norms left out)."""
    return dense_params(c) + held_params(c) \
        + 2 * c["vocab_size"] * c["hidden_size"]


# --- the kernels' needed work: (operations, bytes) a tick, all layers -----
def index_ops_bytes(c: dict, decode: float, chunks: float, chunk: int,
                    context: float) -> tuple:
    """The indexer's scores: every query against the ``context`` keys it
    may see, ``index_n_heads`` products of ``index_head_dim``; a row's live
    keys read once."""
    j, d = c["index_n_heads"], c["index_head_dim"]
    queries = decode + chunks * chunk
    ops = 2.0 * queries * context * j * d
    moved = (decode + chunks) * context * d * BYTES + queries * j * d * BYTES
    return layers_of(c, FULL) * ops, layers_of(c, FULL) * moved


def mla_ops_bytes(c: dict, decode: float, chunks: float, chunk: int,
                  context: float) -> tuple:
    """Absorbed attention over a selection: ``min(context, index_topk)``
    latents a query, scored over ``kv_lora_rank + rope`` and weighed over
    ``kv_lora_rank`` by every head; each query fetches its own."""
    w = _w(c, FULL)
    keys = min(context, c["index_topk"])
    queries = decode + chunks * chunk
    row = w["kv_rank"] + w["rope"]
    ops = 2.0 * queries * w["heads"] * keys * (row + w["kv_rank"])
    moved = queries * keys * row * BYTES
    return layers_of(c, FULL) * ops, layers_of(c, FULL) * moved


def swa_ops_bytes(c: dict, decode: float, chunks: float, chunk: int,
                  context: float) -> tuple:
    """Windowed absorbed attention: ``min(context, window)`` latents a
    query; a row reads its window's latents once."""
    w = _w(c, SLIDING)
    keys = min(context, c["sliding_window_size"])
    queries = decode + chunks * chunk
    row = w["kv_rank"] + w["rope"]
    ops = 2.0 * queries * w["heads"] * keys * (row + w["kv_rank"])
    moved = (decode * keys + chunks * (keys + chunk - 1)) * row * BYTES
    return layers_of(c, SLIDING) * ops, layers_of(c, SLIDING) * moved


def experts_bytes(c: dict, touched_share: float) -> float:
    """The matrices of the held experts that were given a row, once."""
    return touched_share * held_params(c) * BYTES


def least_ms(ops: float, moved: float, peak) -> float:
    """The least time the chip could take: the slower of multiplying and
    moving."""
    return max(ops / peak.bf16_flops, moved / peak.hbm_bytes_per_s) * 1e3


def tick_bytes(c: dict, decode: float, chunks: float, chunk: int,
               context: float, sampled: float, touched_share: float) -> float:
    """Bytes one tick must read and write: every dense weight and the head
    once, the touched experts once, the embedding's rows, the caches the
    three attention parts read and the rows the tick writes."""
    tokens = decode + chunks * chunk
    caches = sum(f(c, decode, chunks, chunk, context)[1]
                 for f in (index_ops_bytes, mla_ops_bytes, swa_ops_bytes))
    full, slide = _w(c, FULL), _w(c, SLIDING)
    written = tokens * BYTES * (
        layers_of(c, FULL) * (full["kv_rank"] + full["rope"]
                              + c["index_head_dim"])
        + layers_of(c, SLIDING) * (slide["kv_rank"] + slide["rope"]))
    head = c["hidden_size"] * c["vocab_size"] * BYTES if sampled else 0.0
    return dense_params(c) * BYTES + experts_bytes(c, touched_share) + head \
        + tokens * c["hidden_size"] * BYTES + caches + written


def tick_flops(c: dict, decode: float, chunks: float, chunk: int,
               context: float, sampled: float, expert_rows: float) -> float:
    """Operations one tick needs: 2 a parameter multiplied a token (the
    dense matrices for every token, an expert for each of the
    ``expert_rows`` rows the held experts were given a layer, the head for
    the ``sampled`` rows) and the three attention parts."""
    tokens = decode + chunks * chunk
    attention = sum(f(c, decode, chunks, chunk, context)[0]
                    for f in (index_ops_bytes, mla_ops_bytes, swa_ops_bytes))
    return 2.0 * dense_params(c) * tokens \
        + 2.0 * expert_params(c) * expert_rows * moe_layers(c) \
        + 2.0 * c["hidden_size"] * c["vocab_size"] * sampled + attention
