"""The plain reference of OLMoE (arXiv:2409.02060; HF ``modeling_olmoe``):
pre-norm blocks of RMSNorm, attention with QK-norm and rotary positions,
and a token-choice mixture of SiLU-gated experts with no token dropped;
final RMSNorm and an untied head. Straight ``jax.numpy`` in float32 at
``highest`` matmul precision: a Python loop over the experts with boolean
masks (each expert computed on every token, one compiled call an expert, so
that nothing unrolls 64 experts into one program), no sort, no kernel, no
cache, no batching trick. It takes the
weights the system holds (under the names ``models/gpt.py`` gives them) and
never its code. ``perfbench/references/olmoe.py`` is a copy.

It follows HF ``modeling_olmoe``. Departures, each for a stated reason:

1. The q, k and v projections are one ``[h, 3 h]`` matrix whose columns are
   laid out ``[3, heads, head_dim]``: storage, not mathematics. q and k are
   RMS-normalised over all ``h`` columns before the heads are split, as HF's
   ``q_norm``/``k_norm`` do.
2. Routing weights stay float32 (HF casts them to the activations' type).
3. The load-balance term is HF ``load_balancing_loss_func`` taken **per
   layer** on that layer's logits, and the layers' terms are averaged. HF
   concatenates all layers' logits and takes one term over the pooled
   tokens; megablocks, which trained the published model, averages
   per-layer terms. A pipeline holds one layer's logits at a time, so the
   per-layer form is what the program computes; the two agree where the
   layers' statistics do. HF's normalisation is kept: ``top_k`` when
   balanced (megablocks' is 1).
4. HF has no router z-loss; arXiv:2409.02060 section 3 trains with one
   (weight 0.001): the mean over tokens of ``logsumexp(router logits)**2``,
   here averaged over the layers like the load-balance term.
5. Both auxiliary terms are taken over all the tokens given in one call
   (``b * s``): the trainer routes one micro-batch at a time and averages
   the micro-batches' terms.
"""
from __future__ import annotations

import functools

import numpy as np


def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    """HF ``OlmoeRMSNorm``."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """HF ``apply_rotary_pos_emb`` on ``x`` [b, s, heads, d], positions
    0..s-1: ``x * cos + rotate_half(x) * sin`` with the frequencies
    ``theta**(-2i/d)`` repeated over the two halves of ``d``."""
    import jax.numpy as jnp

    s, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


#: two routing probabilities closer than this share of their value are a
#: near tie: bf16 activations (2^-8) may order them either way
NEAR_TIE = 2.0 ** -7


@functools.lru_cache(maxsize=None)
def _route_fn(top_k: int):
    import jax

    def route(x, gate):
        logits = x @ gate                                    # [T, E]
        probs = jax.nn.softmax(logits, -1)
        return (logits, probs) + tuple(jax.lax.top_k(probs, top_k))

    return jax.jit(route)


@functools.lru_cache(maxsize=None)
def _expert_fn():
    import jax

    def expert(x, w_gate, w_up, w_down, weight):
        """HF ``OlmoeMLP`` on every token, times the token's routing
        weight for this expert (0 where it was not chosen)."""
        out = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
        return weight[:, None] * out

    # checkpoint: a gradient through the loop recomputes each expert
    # instead of keeping 64 experts' intermediates (5 GB at 4096 tokens)
    return jax.jit(jax.checkpoint(expert))


def moe(x, w, top_k, with_routing=False):
    """HF ``OlmoeSparseMoeBlock`` with ``norm_topk_prob`` false on ``x``
    [T, h]: ``(y [T, h], balance, z)``, and with ``with_routing`` also
    ``rows`` [E], the tokens each expert is given, and ``near_ties``, the
    number of tokens whose last chosen probability and first unchosen one
    differ by under ``NEAR_TIE`` of their value."""
    import jax
    import jax.numpy as jnp

    logits, probs, top_p, top_e = _route_fn(top_k)(x, w["mlp.gate"])
    experts = logits.shape[1]
    y = jnp.zeros_like(x)
    for e in range(experts):
        weight = jnp.sum(jnp.where(top_e == e, top_p, 0.0), -1)  # [T]
        y = y + _expert_fn()(x, w["mlp.w_gate"][e], w["mlp.w_up"][e],
                             w["mlp.w_down"][e], weight)
    # load_balancing_loss_func: tokens_per_expert [k, E], probability [E]
    mask = jax.nn.one_hot(top_e, experts, dtype=jnp.float32)  # [T, k, E]
    balance = experts * jnp.sum(jnp.mean(mask, 0)
                                * jnp.mean(probs, 0)[None, :])
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    if not with_routing:
        return y, balance, z
    edge = jax.lax.top_k(probs, top_k + 1)[0][:, top_k - 1:]  # k-th, k+1-th
    near_ties = jnp.sum(edge[:, 0] - edge[:, 1] < NEAR_TIE * edge[:, 0])
    return y, balance, z, jnp.sum(mask, (0, 1)), near_ties


@functools.lru_cache(maxsize=None)
def _attention_fn(heads: int, eps: float, theta: float):
    import jax
    import jax.numpy as jnp

    def attention(x, w):
        """``x`` [b, s, h] after the attention's residual, and the
        normalised input of the expert layer."""
        b, s, h = x.shape
        d = h // heads
        y = rms_norm(x, w["ln_1.weight"], eps)
        qkv = (y @ w["attn.qkv_proj.weight"]).reshape(b, s, 3, h)
        q = rms_norm(qkv[:, :, 0], w["attn.q_norm.weight"], eps)
        k = rms_norm(qkv[:, :, 1], w["attn.k_norm.weight"], eps)
        q = rope(q.reshape(b, s, heads, d), theta)
        k = rope(k.reshape(b, s, heads, d), theta)
        v = qkv[:, :, 2].reshape(b, s, heads, d)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
        causal = jnp.tril(jnp.ones((s, s), bool))
        att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, h)
        x = x + o @ w["attn.out_proj.weight"]
        return x, rms_norm(x, w["ln_2.weight"], eps)

    return jax.jit(attention)


def block(x, w, heads, top_k, eps, theta):
    """One ``OlmoeDecoderLayer`` on ``x`` [b, s, h]: ``(x, balance, z,
    routing)``; ``routing`` holds the expert layer's input ``x`` and
    output ``y`` ([b s, h]) and ``rows`` and ``near_ties`` as ``moe`` gives
    them."""
    b, s, h = x.shape
    x, y_in = _attention_fn(heads, eps, theta)(
        x, {k: v for k, v in w.items() if not k.startswith("mlp.")})
    y_in = y_in.reshape(b * s, h)
    y, balance, z, rows, near_ties = moe(y_in, w, top_k, with_routing=True)
    return x + y.reshape(b, s, h), balance, z, {
        "x": y_in, "y": y, "rows": rows, "near_ties": near_ties}


def forward(layer_weights, other: dict, tokens, heads: int, top_k: int,
            eps: float = 1e-5, theta: float = 10000.0, routing=None):
    """``(logits [b, s, vocab], balance, z)`` of ``tokens`` [b, s], all
    float32; the two auxiliary terms are means over the layers. A list
    given as ``routing`` is filled with a dict a layer: the expert layer's
    input ``x`` and output ``y`` [b s, h], ``rows`` [E] and ``near_ties``.
    ``layer_weights`` yields one dict a layer (keys as the program names
    them, without the stacking), ``other`` holds the embedding, the final
    norm and the head (``lm_head.weight`` [h, vocab])."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = _f32(other["embeddings.wte.weight"])[np.asarray(tokens)]
        balances, zs = [], []
        for w in layer_weights:
            x, balance, z, layer = block(
                x, {k: _f32(v) for k, v in w.items()}, heads, top_k, eps,
                theta)
            balances.append(balance)
            zs.append(z)
            if routing is not None:
                routing.append({
                    "x": np.asarray(layer["x"]), "y": np.asarray(layer["y"]),
                    "rows": np.asarray(layer["rows"]).astype(np.int64),
                    "near_ties": int(layer["near_ties"])})
        out = rms_norm(x, _f32(other["ln_f.weight"]), eps) \
            @ _f32(other["lm_head.weight"])
    return (jnp.asarray(out, jnp.float32), jnp.mean(jnp.stack(balances)),
            jnp.mean(jnp.stack(zs)))


def next_token_loss(lg, tokens):
    """Mean cross entropy of each position's logits against the next
    token of its sequence. ``lg`` is ``[b, s, vocab]``, ``tokens``
    ``[b, s]``."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(lg[:, :-1], -1)
    nxt = jnp.asarray(np.asarray(tokens)[:, 1:])
    return -jnp.take_along_axis(logp, nxt[..., None], -1).mean()


def loss_terms(layer_weights, other, tokens, heads, top_k, eps=1e-5,
               theta=10000.0, balance_weight=0.01, z_weight=0.001) -> dict:
    """The training loss of arXiv:2409.02060 and its parts, as floats:
    ``ce + balance_weight * balance + z_weight * z``; and ``routing``,
    a dict a layer as ``forward`` fills it."""
    routing = []
    lg, balance, z = forward(layer_weights, other, tokens, heads, top_k,
                             eps, theta, routing)
    ce = float(next_token_loss(lg, tokens))
    balance, z = float(balance), float(z)
    return {"ce": ce, "balance": balance, "z": z, "routing": routing,
            "loss": ce + balance_weight * balance + z_weight * z}
