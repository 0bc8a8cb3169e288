"""The plain reference of Olmo-Hybrid for the benchmark's check: a copy of
``paddle_tpu/models/olmo_hybrid_reference.py`` (tests/perfbench pins the two
equal from the equations on), kept under ``perfbench/`` so that the
comparison that decides ``correct`` imports none of the program's code.
Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: one full
causal forward over prompt and output together, the delta rule token by
token, no chunking, no cache, no kernel, no batching. It takes the weights
the system holds, by the names the program gives them.

With ``N(.)`` an RMSNorm with its own weight (eps 1e-6) and every matrix
without bias::

    linear layer (30 heads, keys of 96, values of 192; [q~|k~|v~] = x W_qkv):
        [q~|k~|v~] <- SiLU(conv4([q~|k~|v~]))       # depthwise, causal, 4 taps
        q_h = q~_h / sqrt(|q~_h|^2 + 1e-6) ; k_h likewise ; v_h = v~_h
        [a|b] = x W_ab ; beta_h = 2 sigmoid(b_h)     # linear_allow_neg_eigval
        g_h = -exp(A_log_h) softplus(a_h + dt_bias_h)
        S_h(t) = e^{g_h} (I - beta_h k_h k_h^T) S_h(t-1) + beta_h k_h v_h^T
                                                    # S_h(-1) = 0
        o_h = S_h(t)^T q_h / sqrt(96)
        mixer = [N_192(o_h) * SiLU((x W_g)_h)]_h W_o

    full layer (30 heads of 128, no rotary: rope_theta is null):
        [q|k|v] = x W_qkv ; q <- N_3840(q) ; k <- N_3840(k)
        o_i(t) = sum_{s <= t} softmax_s(q_i(t) . k_i(s) / sqrt(128)) v_i(s)
        mixer = [o_1..o_30] W_o

    block: h = x + N(mixer(x)) ; x <- h + N(W_down(SiLU(W_gate h) * W_up h))
    logits = N_f(x) W_head

``control`` names a wrong model, for the checks that must tell it from the
right one: ``"bf16_state"`` (the state rounded to bfloat16 after every
token), ``"state_not_carried"`` (the state starts at zero again at every
chunk of the prompt), ``"no_decay"`` (``g = 0``), ``"beta_not_doubled"``
(``beta = sigmoid(b)``), ``"conv_history_dropped"`` (the convolution sees
zeros before a tick's first token: every chunk of the prompt, every decoded
token), ``"rope"`` (rotary at theta 5e5 in the full layers). The two that
speak of ticks read ``ticks = (prompt tokens, chunk)``. fp8 weights are the
caller's rounding of what it passes.
"""
from __future__ import annotations

import functools

import numpy as np

CONTROLS = (None, "bf16_state", "state_not_carried", "no_decay",
            "beta_not_doubled", "conv_history_dropped", "rope")


def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _static(config: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "linear_allow_neg_eigval",
            "rms_norm_eps")
    return tuple(config[k] for k in keys)


def _tick_start(s: int, ticks):
    """[s] int32: the first position of the tick that brought each one."""
    import jax.numpy as jnp

    pos = jnp.arange(s, dtype=jnp.int32)
    if ticks is None:
        return jnp.zeros((s,), jnp.int32)
    prompt, chunk = ticks
    return jnp.where(pos < prompt, pos // chunk * chunk, pos)


@functools.lru_cache(maxsize=None)
def _linear_fn(cfg: tuple, control, ticks):
    import jax
    import jax.numpy as jnp

    _, _, heads, dk, dv, taps, neg, eps = cfg
    kw = heads * dk

    def mixer(x, p, n_live):
        s = x.shape[0]
        pos = jnp.arange(s, dtype=jnp.int32)
        start = _tick_start(s, ticks)
        qkv = x @ p["mix.qkv.weight"]
        padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
        y = 0.0
        for j in range(taps):
            back = taps - 1 - j                 # the tap's distance back
            term = padded[j:j + s] * p["mix.conv.weight"][j]
            if control == "conv_history_dropped":
                term = jnp.where((pos - back >= start)[:, None], term, 0.0)
            y = y + term
        y = jax.nn.silu(y)
        l2 = lambda a: a * jax.lax.rsqrt(                   # noqa: E731
            jnp.sum(a * a, -1, keepdims=True) + 1e-6)
        q = l2(y[:, :kw].reshape(s, heads, dk))
        k = l2(y[:, kw:2 * kw].reshape(s, heads, dk))
        v = y[:, 2 * kw:].reshape(s, heads, dv)
        ab = x @ p["mix.ab.weight"]
        beta = jax.nn.sigmoid(ab[:, heads:])
        if neg and control != "beta_not_doubled":
            beta = 2.0 * beta
        g = -jnp.exp(p["mix.A_log.weight"]) * jax.nn.softplus(
            ab[:, :heads] + p["mix.dt_bias.weight"])
        if control == "no_decay":
            g = jnp.zeros_like(g)
        reset = (pos == start) & (pos > 0) if control == "state_not_carried" \
            else jnp.zeros((s,), bool)

        def step(S, t):
            qt, kt, vt, gt, bt, live, zero = t
            S0 = jnp.where(zero, 0.0, S)
            S1 = jnp.exp(gt)[:, None, None] * S0
            S1 = S1 + (bt[:, None] * kt)[:, :, None] * (
                vt - jnp.einsum("hk,hkv->hv", kt, S1))[:, None, :]
            if control == "bf16_state":     # (a cast there and back is
                # excess precision to XLA, which drops the pair on the TPU)
                S1 = jax.lax.reduce_precision(S1, exponent_bits=8,
                                              mantissa_bits=7)
            o = jnp.einsum("hk,hkv->hv", qt, S1) * dk ** -0.5
            return jnp.where(live, S1, S), o

        S, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), jnp.float32),
                            (q, k, v, g, beta, pos < n_live, reset))
        o = rms_norm(o, p["mix.o_norm.weight"], eps)
        gate = jax.nn.silu(x @ p["mix.gate.weight"]).reshape(s, heads, dv)
        return (o * gate).reshape(s, -1) @ p["mix.o.weight"], S

    return jax.jit(mixer)


def _rope(x, theta: float):
    """Rotate-half rotary over ``x`` [s, heads, d] by position."""
    import jax.numpy as jnp

    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


@functools.lru_cache(maxsize=None)
def _full_fn(cfg: tuple, control):
    import jax
    import jax.numpy as jnp

    h, heads, _, _, _, _, _, eps = cfg
    d = h // heads

    def mixer(x, p):
        s = x.shape[0]
        qkv = x @ p["attn.qkv.weight"]
        q = rms_norm(qkv[:, :h], p["attn.q_norm.weight"], eps)
        k = rms_norm(qkv[:, h:2 * h], p["attn.k_norm.weight"], eps)
        q, k, v = (a.reshape(s, heads, d) for a in (q, k, qkv[:, 2 * h:]))
        if control == "rope":
            q, k = _rope(q, 5e5), _rope(k, 5e5)
        keep = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

        def head(x):                    # a head at a time: [s, s] scores
            qh, kh, vh = x
            scores = (qh @ kh.T) * d ** -0.5
            return jax.nn.softmax(jnp.where(keep, scores, -jnp.inf),
                                  axis=-1) @ vh

        o = jax.lax.map(head, tuple(jnp.swapaxes(a, 0, 1)
                                    for a in (q, k, v)))      # [n, s, d]
        return jnp.swapaxes(o, 0, 1).reshape(s, h) @ p["attn.o.weight"]

    return jax.jit(mixer)


@functools.lru_cache(maxsize=None)
def _ffn_fn(eps: float):
    import jax

    def ffn(x, out, p):
        h = x + rms_norm(out, p["ln_1.weight"], eps)
        mid = jax.nn.silu(h @ p["ffn.fc_gate.weight"]) \
            * (h @ p["ffn.fc_in.weight"])
        return h + rms_norm(mid @ p["ffn.fc_out.weight"], p["ln_2.weight"],
                            eps)

    return jax.jit(ffn)


_MIXER = ("mix.", "attn.")


def forward(layers, other: dict, tokens, config: dict, n_live=None,
            control=None, ticks=None) -> dict:
    """The full causal forward over ``tokens`` [s]. ``layers`` yields one
    ``(kind, weights)`` a layer: ``"linear_attention"`` or
    ``"full_attention"``, and its weights by the names the program gives
    them; ``other`` holds the embedding, the final norm and the head;
    ``config`` the sizes under the keys of ``config.json``. The first
    ``n_live`` positions (default: all) move the recurrent states: a caller
    that pads ``tokens`` passes the true length. Returns float32 ``state``
    [s, h] (what the head reads) and ``states``, one ``[heads, dk, dv]``
    float32 array a linear layer: its recurrent state after position
    ``n_live - 1``."""
    import jax

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    tokens = np.asarray(tokens).reshape(-1)
    n_live = len(tokens) if n_live is None else int(n_live)
    cfg = _static(config)
    ticks = None if ticks is None else tuple(int(t) for t in ticks)
    states = []
    with jax.default_matmul_precision("highest"):
        x = _f32(other["embeddings.wte.weight"][tokens])
        for kind, w in layers:
            # a half's weights at a time: ``w`` may fetch a name when asked
            p = {k: _f32(w[k]) for k in w if k.startswith(_MIXER)}
            if kind == "full_attention":
                out = _full_fn(cfg, control)(x, p)
            else:
                out, S = _linear_fn(cfg, control, ticks)(
                    x, p, np.int32(n_live))
                states.append(S)
            p = {k: _f32(w[k]) for k in w if not k.startswith(_MIXER)}
            x = _ffn_fn(cfg[-1])(x, out, p)
            del p
        state = jax.jit(rms_norm, static_argnums=2)(
            x, _f32(other["ln_f.weight"]), cfg[-1])
    return {"state": state, "states": states}


#: columns of the head a product takes at a time: float32 of the whole
#: 3,840 x 100,352 need not stand beside an engine's pools
_HEAD_BLOCK = 16384


def _head_blocks(state, other: dict):
    """``state`` [n, h] float32 times the head, ``_HEAD_BLOCK`` columns at a
    time: ``(first column, [n, block] float32 logits)``."""
    import jax

    head = other["lm_head.weight"]
    with jax.default_matmul_precision("highest"):
        for lo in range(0, head.shape[1], _HEAD_BLOCK):
            yield lo, _f32(state) @ _f32(head[:, lo:lo + _HEAD_BLOCK])


def logits(state, other: dict):
    """``[s, vocab]`` float32 logits of ``forward``'s ``state``."""
    return np.concatenate([np.asarray(b)
                           for _, b in _head_blocks(state, other)], -1)


def shortfall(state, other: dict, targets):
    """For each position of ``state`` [n, h]: how far its logit for
    ``targets`` [n] lies below its largest logit, and that logit itself;
    both ``[n]`` float32 on the host."""
    targets = np.asarray(targets)
    top = np.full(targets.shape, -np.inf, np.float32)
    mine = np.zeros(targets.shape, np.float32)
    for lo, block in _head_blocks(state, other):
        block = np.asarray(block)
        top = np.maximum(top, block.max(-1))
        here = (targets >= lo) & (targets < lo + block.shape[1])
        mine[here] = block[here, targets[here] - lo]
    return top - mine, mine
