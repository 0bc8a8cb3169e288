"""The plain reference of the dense GPT of ``models/gpt.py``: pre-norm
blocks, fused QKV with the heads laid out ``[3, heads, head_dim]``, tanh
GELU, learned positions, tied head. Straight ``jax.numpy`` in float32 at
``highest`` matmul precision, one layer at a time, no kernel, no cache, no
batching. It takes the weights the system holds and never its code.
"""
from __future__ import annotations

import functools

import numpy as np


def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


@functools.lru_cache(maxsize=None)
def _block_fn(heads: int, eps: float):
    import jax
    import jax.numpy as jnp

    def block(x, w):
        s, h = x.shape
        d = h // heads
        y = _layer_norm(x, w["ln_1.weight"], w["ln_1.bias"], eps)
        qkv = y @ w["attn.qkv_proj.weight"] + w["attn.qkv_proj.bias"]
        q, k, v = jnp.moveaxis(qkv.reshape(s, 3, heads, d), 1, 0)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(d))
        mask = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        att = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h)
        x = x + att @ w["attn.out_proj.weight"] + w["attn.out_proj.bias"]
        y = _layer_norm(x, w["ln_2.weight"], w["ln_2.bias"], eps)
        y = jax.nn.gelu(y @ w["mlp.fc_in.weight"] + w["mlp.fc_in.bias"],
                        approximate=True)
        return x + y @ w["mlp.fc_out.weight"] + w["mlp.fc_out.bias"]

    return jax.jit(jax.vmap(block, in_axes=(0, None)))


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float):
    import jax

    def head(x, ln_w, ln_b, wte):
        return _layer_norm(x, ln_w, ln_b, eps) @ wte.T

    return jax.jit(head)


def logits(layer_weights, other: dict, tokens, heads: int,
           eps: float = 1e-5):
    """``[b, s, vocab]`` float32 logits of ``tokens`` ``[b, s]``.
    ``layer_weights`` yields one dict a layer (keys as the program names
    them, without the stacking), ``other`` holds the embeddings and the
    final norm."""
    import jax
    import jax.numpy as jnp

    tokens = np.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        x = _f32(other["embeddings.wte.weight"])[tokens] + \
            _f32(other["embeddings.wpe.weight"])[:tokens.shape[1]]
        for w in layer_weights:
            x = _block_fn(heads, eps)(x, {k: _f32(v) for k, v in w.items()})
        out = _head_fn(eps)(x, _f32(other["ln_f.weight"]),
                            _f32(other["ln_f.bias"]),
                            _f32(other["embeddings.wte.weight"]))
    return jnp.asarray(out, jnp.float32)


def next_token_loss(lg, tokens) -> float:
    """Mean cross entropy of each position's logits against the next
    token of its sequence, as ``GPT.pipeline_head`` defines the training
    loss. ``lg`` is ``[b, s, vocab]``, ``tokens`` ``[b, s]``."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(lg[:, :-1], -1)
    nxt = jnp.asarray(np.asarray(tokens)[:, 1:])
    picked = jnp.take_along_axis(logp, nxt[..., None], -1)
    return float(-picked.mean())


@functools.lru_cache(maxsize=None)
def _shortfall_fn():
    import jax
    import jax.numpy as jnp

    def f(lg, targets, mask):
        got = jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
        return jnp.where(mask, lg.max(-1) - got, 0.0)

    return jax.jit(f)


def shortfall(lg, targets, mask):
    """How far each position's logit for ``targets`` lies below that
    position's largest logit, ``[b, s]`` on the host; 0 where ``mask`` is
    false. One program, so that checking costs one compilation."""
    import jax.numpy as jnp

    return np.asarray(_shortfall_fn()(lg, jnp.asarray(targets),
                                      jnp.asarray(mask)))
