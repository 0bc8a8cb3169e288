"""Operation and byte counts of a token-choice mixture of experts, from the
sizes in a configuration file (under the keys of HF's ``config.json``, as
the file states them), with the source of each count. Beside
``yardstick.py``, which it does not change and whose peaks it uses.
"""
from __future__ import annotations


def olmoe_params(c: dict) -> dict:
    """Parameters of OLMoE as ``models/gpt.py`` builds it from the file's
    sizes (HF ``modeling_olmoe``: no biases, RMSNorm weights, q/k norms
    ``hidden`` wide, untied head): ``total``, and ``active``, what one
    token's forward pass multiplies with: ``num_experts_per_tok`` of the
    ``num_experts`` experts, and the embedding row it reads is not a
    multiplication."""
    h, layers, v = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    f, e, k = c["intermediate_size"], c["num_experts"], c["num_experts_per_tok"]
    attention = 4 * h * h + 2 * h          # q, k, v, o; q_norm, k_norm
    norms = 2 * h
    router = h * e
    expert = 3 * h * f                     # gate, up, down
    total = 2 * v * h + layers * (attention + norms + router + e * expert) + h
    active = v * h + layers * (attention + norms + router + k * expert) + h
    return {"total": total, "active": active,
            "layer": attention + norms + router + e * expert}


def olmoe_train_flops_per_token(c: dict, seq: int) -> float:
    """Operations the forward and backward passes need for one token: 6 for
    every active parameter (2 forward, 4 backward; Kaplan et al.,
    arXiv:2001.08361 section 2.1) and ``12 L h s`` for attention
    (Megatron's count, as ``yardstick.gpt_train_flops_per_token``).
    Recomputation is not counted, nor the routing, which multiplies
    nothing but the router."""
    return 6.0 * olmoe_params(c)["active"] + \
        12.0 * c["num_hidden_layers"] * c["hidden_size"] * seq


def expert_ops_bytes(tokens: int, c: dict, itemsize: int = 2,
                     backward: bool = True) -> tuple:
    """(operations, bytes) the three expert products of ONE layer need for
    ``tokens`` tokens. Forward: gate, up and down, each ``2 * rows * h *
    f`` with ``rows = tokens * num_experts_per_tok``. Backward: two products
    of that size for each forward one (towards the rows and towards the
    weights), so three times the forward in all. Bytes, forward: every
    expert's three matrices read once, the gathered rows read twice and
    the two ``[rows, f]`` results and the ``[rows, h]`` result written,
    the ``[rows, f]`` product read; the backward pass is counted as twice
    that. A grouped matmul is a plain one per expert (Gale et al.,
    MegaBlocks, arXiv:2211.15841 section 4)."""
    h, f = c["hidden_size"], c["intermediate_size"]
    rows = tokens * c["num_experts_per_tok"]
    ops = 3 * 2.0 * rows * h * f
    data = itemsize * (3.0 * c["num_experts"] * h * f
                       + 2.0 * rows * h + 3.0 * rows * f + rows * h)
    return (3.0 * ops, 3.0 * data) if backward else (ops, data)


def experts_roofline_pct(ms_per_step: float, tokens_per_micro: int,
                         n_micro: int, c: dict, peak) -> float:
    """The least time the chip could take for a step's expert products,
    forward and backward of every layer and micro-batch (recomputation
    not counted), over the time measured, in per cent: the larger of
    operations over ``peak.bf16_flops`` and bytes over
    ``peak.hbm_bytes_per_s``."""
    ops, data = expert_ops_bytes(tokens_per_micro, c)
    calls = c["num_hidden_layers"] * n_micro
    least_s = max(ops / peak.bf16_flops, data / peak.hbm_bytes_per_s) * calls
    return 100.0 * least_s / (ms_per_step / 1e3)
