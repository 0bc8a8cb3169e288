"""The plain reference of Ouro, a looped language model (arXiv:2510.25741,
"Scaling Latent Reasoning via Looped Language Models"; config.json of
huggingface.co/ByteDance/Ouro-2.6B): the same ``L`` layers run ``T`` times
over a token, the final norm and an exit gate after every run. Straight
``jax.numpy`` in float32 at ``highest`` matmul precision: one full causal
forward over prompt and output together, a layer's float32 weights at a
time, no cache, no kernel. It takes the weights the system holds (under the
names ``models/gpt.py`` gives them) and never its code.
``perfbench/references/ouro.py`` is a copy.

With ``N(.)`` an RMSNorm with its own weight (float32 statistics, eps
1e-6), ``E`` the embedding and ``h = E[tokens]``, for loop step
``t = 1..T``::

    for layer l = 1..L (the same L layers' weights at every t):
        q, k, v = W_q N1_l(h), W_k N1_l(h), W_v N1_l(h)      # no bias
        q, k    = rope(q, pos), rope(k, pos)      # rotate-half, theta 1e6
        a       = W_o softmax(q k^T / sqrt(d), causal) v
                  # the system keeps k and v in cache (t-1) L + l
        h       = h + N2_l(a)                     # sandwich norm
        m       = W_down(silu(W_gate N3_l(h)) * (W_up N3_l(h)))
        h       = h + N4_l(m)
    h   = N_f(h)            # after every step; its output starts step t+1
    s_t = h                 # what the head may read
    g_t = sigmoid(w_g . h + b_g)                  # one gate for all steps

Exit rule, per position: ``p_t = g_t prod_{j<t}(1 - g_j)`` for ``t < T``
and ``p_T`` the remainder; the position exits at the first ``t`` whose
cumulative ``p`` reaches the threshold, else at ``T``; ``logits = W_head
s_exit``. All ``T`` steps always run (later tokens attend to every step's
keys), so at the published threshold 1.0 every position reads ``s_T`` and
the gate's values are reported, not acted on.

Assumed (the catalog has config.json only; these are the published
modeling code and the paper as ISSUE 33's writer read them): no biases on
the seven projections; the sandwich norms ``N2``, ``N4``; the final norm's
output feeding the next step; the gate's form and the exit rule; a cache
for every (step, layer). The paper's cache-sharing variants for decoding
(last step only, or averaged) are not followed: the published
implementation keeps all ``T``.

Departure: the q, k and v projections are one ``[h, 3 h]`` matrix whose
columns are laid out ``[3, heads, head_dim]``: storage, not mathematics.

``control`` names a wrong model, for the checks that must tell it from the
right one: ``"unrotated_keys"`` (attention over keys that were cached before
the rotation), ``"shared_cache"`` (every step after the first reads the
first step's keys and values: cache index ``l`` in place of ``(t-1) L +
l``). Three steps in place of four is ``steps=3``; fp8 weights are the
caller's rounding of what it passes.
"""
from __future__ import annotations

import functools

import numpy as np

CONTROLS = (None, "unrotated_keys", "shared_cache")


def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """HF ``apply_rotary_pos_emb`` on ``x`` [b, s, heads, d], positions
    0..s-1: ``x * cos + rotate_half(x) * sin`` with the frequencies
    ``theta**(-2i/d)`` repeated over the two halves of ``d``."""
    import jax.numpy as jnp

    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


@functools.lru_cache(maxsize=None)
def _block_fn(heads: int, eps: float, theta: float, control):
    import jax
    import jax.numpy as jnp

    def block(x, w, kv):
        """One layer over ``x`` [b, s, h]. ``kv``: the keys and values the
        attention reads in place of its own (``shared_cache``), or None.
        Returns the layer's output and its own keys and values."""
        b, s, h = x.shape
        d = h // heads
        y = rms_norm(x, w["ln_1.weight"], eps)
        qkv = (y @ w["attn.qkv_proj.weight"]).reshape(b, s, 3, heads, d)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        q = rope(q, theta)
        if control != "unrotated_keys":
            k = rope(k, theta)
        mine = (k, v)
        if kv is not None:
            k, v = kv
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
        mask = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        a = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h)
        x = x + rms_norm(a @ w["attn.out_proj.weight"],
                         w["post_attn_norm.weight"], eps)
        y = rms_norm(x, w["ln_2.weight"], eps)
        m = (jax.nn.silu(y @ w["mlp.fc_gate.weight"])
             * (y @ w["mlp.fc_in.weight"])) @ w["mlp.fc_out.weight"]
        return x + rms_norm(m, w["post_ffn_norm.weight"], eps), mine

    return jax.jit(block)


@functools.lru_cache(maxsize=None)
def _step_end_fn(eps: float):
    import jax

    def end(x, ln_f, w_g, b_g):
        x = rms_norm(x, ln_f, eps)
        return x, jax.nn.sigmoid(x @ w_g[:, 0] + b_g[0])

    return jax.jit(end)


def exit_rule(states, gates, threshold: float):
    """``states`` [T, ..., h], ``gates`` [T, ...] -> (the chosen step's
    state, the expected exit step ``sum_t t p_t``, the chosen step), steps
    counted from 1."""
    import jax.numpy as jnp

    steps = states.shape[0]
    p, left = [], jnp.ones_like(gates[0])
    for t in range(steps - 1):
        p.append(gates[t] * left)
        left = left * (1.0 - gates[t])
    p.append(left)                                   # the remainder
    expected = sum((t + 1) * p[t] for t in range(steps))
    chosen = jnp.full(gates[0].shape, steps, jnp.int32)
    cum = jnp.zeros_like(gates[0])
    for t in range(steps - 1):
        cum = cum + p[t]
        chosen = jnp.where((cum >= threshold) & (chosen == steps), t + 1,
                           chosen)
    out = states[steps - 1]
    for t in range(steps - 1):
        out = jnp.where((chosen == t + 1)[..., None], states[t], out)
    return out, expected, chosen


def forward(layers, other: dict, tokens, heads: int, steps: int,
            threshold: float = 1.0, eps: float = 1e-6,
            theta: float = 1000000.0, control=None) -> dict:
    """The full causal forward over ``tokens`` [b, s]. ``layers()`` yields
    one dict a layer (keys as the program names them, without the
    stacking) and is called once a loop step; ``other`` holds the
    embedding, the final norm, the exit gate and the head. Returns float32
    ``state`` [b, s, h] (the exit rule's step, what the head reads),
    ``gates`` [T, b, s], ``expected`` and ``chosen`` [b, s]."""
    import jax
    import jax.numpy as jnp

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    tokens = np.asarray(tokens)
    block = _block_fn(heads, float(eps), float(theta), control)
    with jax.default_matmul_precision("highest"):
        x = _f32(other["embeddings.wte.weight"])[tokens]
        states, gates, first = [], [], []
        for t in range(steps):
            for i, w in enumerate(layers()):
                kv = first[i] if control == "shared_cache" and t else None
                x, mine = block(x, {k: _f32(v) for k, v in w.items()}, kv)
                if control == "shared_cache" and not t:
                    first.append(mine)
            x, g = _step_end_fn(float(eps))(
                x, _f32(other["ln_f.weight"]),
                _f32(other["exit_gate.weight"]),
                _f32(other["exit_gate.bias"]))
            states.append(x)
            gates.append(g)
        gates = jnp.stack(gates)
        state, expected, chosen = exit_rule(jnp.stack(states), gates,
                                            threshold)
    return {"state": state, "gates": gates, "expected": expected,
            "chosen": chosen}


def logits(state, other: dict):
    """``[b, s, vocab]`` float32 logits of ``forward``'s ``state``."""
    import jax

    with jax.default_matmul_precision("highest"):
        return state @ _f32(other["lm_head.weight"])


@functools.lru_cache(maxsize=None)
def _shortfall_fn():
    import jax
    import jax.numpy as jnp

    def f(state, head, lo, targets, best, got):
        lg = state @ head                            # [b, s, chunk]
        inside = (targets >= lo) & (targets < lo + head.shape[1])
        mine = jnp.take_along_axis(
            lg, jnp.clip(targets - lo, 0, head.shape[1] - 1)[..., None],
            -1)[..., 0]
        return (jnp.maximum(best, lg.max(-1)),
                jnp.where(inside, mine, got))

    return jax.jit(f)


def shortfall(state, other: dict, targets, mask, chunk: int = 8192):
    """How far each position's logit for ``targets`` lies below that
    position's largest logit, ``[b, s]`` on the host; 0 where ``mask`` is
    false. The head is taken ``chunk`` columns at a time, so that neither
    it nor the logits are ever whole in float32."""
    import jax
    import jax.numpy as jnp

    head = other["lm_head.weight"]
    targets = jnp.asarray(targets)
    best = jnp.full(targets.shape, -jnp.inf, jnp.float32)
    got = jnp.zeros(targets.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        for lo in range(0, head.shape[1], chunk):
            best, got = _shortfall_fn()(state, _f32(head[:, lo:lo + chunk]),
                                        lo, targets, best, got)
    return np.where(np.asarray(mask), np.asarray(best - got), 0.0)
