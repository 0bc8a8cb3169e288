"""Operation and byte counts of Solar-Open2's parts, from the sizes in a
configuration file (under the keys of the model's ``config.json``, as the
file states them), with the source of each count. Beside ``yardstick.py``,
which it does not change and whose peaks it uses.
"""
from __future__ import annotations

#: tokens a chunk of the chunked gated delta rule (arXiv:2510.26692
#: section 3; ``fla.ops.kda`` and ``paddle_tpu/ops/kda.py`` both take 64)
CHUNK = 64


def kda_ops_bytes(tokens: int, c: dict, itemsize: int = 2,
                  backward: bool = True) -> tuple:
    """(operations, bytes) the gated delta-rule scan of ONE layer needs for
    one sequence of ``tokens`` tokens, forward and, with ``backward``, both
    passes. A head's chunk of ``C`` tokens with keys of ``dk`` and values of
    ``dv`` (arXiv:2510.26692 section 3, the chunkwise form):

    * the two pair matrices (keys against keys, queries against keys), of
      which the lower triangles are needed: ``2 * C*C*dk``;
    * the unit lower-triangular system, by substitution: ``C**3 / 3``
      (Golub and Van Loan, Matrix Computations, 3.1: a triangular inverse);
    * three products with the state, ``(k exp G) S0``, ``(q exp G) S0`` and
      ``k_end^T u``: ``3 * 2 * C*dk*dv``;
    * two triangular products of ``[C, C]`` with ``[C, dv]``, the system's
      inverse on its right side and ``Aq u``: ``2 * C*C*dv``.

    Backward: two products for each forward one (towards either operand),
    so three times the forward in all; the chunk states recomputed there
    are recomputation and are not counted. Bytes, forward: q, k, v read
    and o written in ``itemsize`` bytes, the log-decay ``g`` read in float32
    (it is summed over a chunk, which bf16 cannot carry), ``beta`` in
    float32; backward: those five and ``do`` read, five gradients written,
    ``dg`` and ``dbeta`` in float32. The state never leaves the chip's
    fast memory inside a sequence."""
    lin = c["linear_attn_config"]
    heads, dk = lin["num_heads"], lin["head_dim"]
    dv = dk
    chunks = tokens / CHUNK * heads
    fwd_ops = chunks * (2.0 * CHUNK * CHUNK * dk + CHUNK ** 3 / 3.0
                        + 6.0 * CHUNK * dk * dv + 2.0 * CHUNK * CHUNK * dv)
    rows = tokens * heads
    fwd_bytes = rows * (itemsize * (2 * dk + 2 * dv) + 4 * dk + 4)
    if not backward:
        return fwd_ops, fwd_bytes
    bwd_bytes = rows * (itemsize * (2 * dk + 2 * dv) + 4 * dk + 4
                        + itemsize * (2 * dk + dv) + 4 * dk + 4)
    return 3.0 * fwd_ops, fwd_bytes + bwd_bytes


def softmax_layers(c: dict) -> int:
    """``gqa_layers`` is the published list; those under the depth held."""
    return sum(i < c["num_hidden_layers"] for i in c["gqa_layers"])


def linear_layers(c: dict) -> int:
    return c["num_hidden_layers"] - softmax_layers(c)


def scan_roofline_pct(ms_per_step: float, seq: int, sequences: int, c: dict,
                      peak) -> float:
    """The least time the chip could take for a step's scans, forward and
    backward of every linear-attention layer and sequence (recomputation
    not counted, so a step that recomputes the forward pass cannot pass
    the forward's share of the whole), over the time measured, in per
    cent. Forward and backward are bounded apart: each by the larger of
    its operations over ``peak.bf16_flops`` and its bytes over
    ``peak.hbm_bytes_per_s``."""
    f_ops, f_bytes = kda_ops_bytes(seq, c, backward=False)
    ops, data = kda_ops_bytes(seq, c)
    least = max(f_ops / peak.bf16_flops, f_bytes / peak.hbm_bytes_per_s) \
        + max((ops - f_ops) / peak.bf16_flops,
              (data - f_bytes) / peak.hbm_bytes_per_s)
    return 100.0 * least * linear_layers(c) * sequences / (ms_per_step / 1e3)


def held_experts_ops_bytes(rows: float, calls: int, c: dict,
                           itemsize: int = 2) -> tuple:
    """(operations, bytes) the held experts' three products need in a step
    that gave them ``rows`` rows over ``calls`` expert-layer calls (layers x
    micro-batches), forward and backward, counted as
    ``yardstick_moe.expert_ops_bytes`` counts the layer that holds every
    expert: forward ``3 * 2 * rows * h * f`` and twice that backward;
    bytes, forward: the ``n_routed_experts`` held experts' three matrices
    read once a call, the gathered rows read twice, the two ``[rows, f]``
    results and the ``[rows, h]`` result written, the ``[rows, f]`` product
    read; the backward pass twice that."""
    h, f = c["hidden_size"], c["moe_intermediate_size"]
    ops = 3 * 2.0 * rows * h * f
    data = itemsize * (3.0 * c["n_routed_experts"] * h * f * calls
                       + 2.0 * rows * h + 3.0 * rows * f + rows * h)
    return 3.0 * ops, 3.0 * data


def held_experts_roofline_pct(ms_per_step: float, rows: float, n_micro: int,
                              c: dict, peak) -> float:
    """The least time for a step's held-expert products (the larger of
    operations over ``peak.bf16_flops`` and bytes over
    ``peak.hbm_bytes_per_s``) over the time measured, in per cent."""
    ops, data = held_experts_ops_bytes(
        rows, c["num_hidden_layers"] * n_micro, c)
    least_s = max(ops / peak.bf16_flops, data / peak.hbm_bytes_per_s)
    return 100.0 * least_s / (ms_per_step / 1e3)


def params_multiplied_here(c: dict) -> dict:
    """Parameters one token's forward pass multiplies with on this chip,
    by part, as ``models/solar_open2.py`` builds the model from the file's
    sizes: the attention halves whole, every layer's router and shared
    expert, of the routed experts the ``num_experts_per_tok *
    n_routed_experts / published.n_routed_experts`` a token meets here on
    average (8 of 320 held: 0.2 experts a token a layer), and the head
    over the vocabulary held. The embedding's lookup is no product."""
    h, f = c["hidden_size"], c["moe_intermediate_size"]
    lin = c["linear_attn_config"]
    r = c["assumed_sizes"]["kda_proj_rank"]
    qd = c["num_attention_heads"] * c["head_dim"]
    kvd = c["num_key_value_heads"] * c["head_dim"]
    ld = lin["num_heads"] * lin["head_dim"]
    routers = c["published"]["n_routed_experts"]
    gqa = 3 * h * qd + 2 * h * kvd                  # q, gate, o; k, v
    kda = 4 * h * ld + 2 * (h * r + r * ld) + h * lin["num_heads"] \
        + 3 * lin["short_conv_kernel_size"] * ld    # q, k, v, o; f, g; b
    met = c["num_experts_per_tok"] * c["n_routed_experts"] / routers
    experts = h * routers + 3 * h * f * (c["n_shared_experts"] + met)
    layers = c["num_hidden_layers"]
    return {"attention": softmax_layers(c) * gqa
            + linear_layers(c) * kda,
            "experts": layers * experts, "head": c["vocab_size"] * h}


def train_flops_per_token(c: dict, seq: int) -> float:
    """Operations the forward and backward passes need for one token here:
    6 for every parameter it multiplies with (2 forward, 4 backward;
    Kaplan et al., arXiv:2001.08361 section 2.1), ``12 * h' * s`` for each
    softmax layer with ``h'`` the heads' total width (Megatron's count, as
    ``yardstick.gpt_train_flops_per_token`` takes it) and the scans'
    operations (``kda_ops_bytes``). Recomputation is not counted."""
    wide = c["num_attention_heads"] * c["head_dim"]
    scan = kda_ops_bytes(seq, c)[0] / seq * linear_layers(c)
    return 6.0 * sum(params_multiplied_here(c).values()) \
        + 12.0 * softmax_layers(c) * wide * seq + scan
