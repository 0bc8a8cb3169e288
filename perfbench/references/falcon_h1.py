"""The plain reference of Falcon-H1 for the benchmark's check: a copy of
``paddle_tpu/models/falcon_h1_reference.py`` (tests/perfbench pins the two
equal from the equations on), kept under ``perfbench/`` so that the
comparison that decides ``correct`` imports none of the program's code.
Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: one full
causal forward over prompt and output together, the state-space rule token by
token, no chunks, no cache, no kernel, no batching. It takes the weights the
system holds, by the names the program gives them.

With ``N(.)`` an RMSNorm with its own weight (eps 1e-5), every matrix without
bias, ``H = 32`` heads of ``P = 128`` channels, a state of ``N = 256``, ``G =
2`` groups (head ``i`` reads group ``i // (H / G)``)::

    e      = Embed[ids] * embedding_multiplier
    n      = N_1(x)
    u      = (n * ssm_in_multiplier) W_in          # [z 4096 | x 4096 | B 512 | C 512 | dt 32]
    u      = u * mup                               # ssm_multipliers[0..4] on z, x, B, C, dt
    [x|B|C] <- SiLU(conv4([x|B|C]) + conv_bias)    # depthwise, causal, 4 taps
    dt_i   = softplus(dt_i + dt_bias_i) ;  A_i = -exp(A_log_i)
    S_i(t) = exp(dt_i A_i) S_i(t-1) + dt_i x_i B_g^T       # [P, N], S_i(-1) = 0
    y_i    = S_i(t) C_g + D_i x_i
    m      = GroupN(y * SiLU(z)) W_out * ssm_out_multiplier    # groups of d_ssm / G
    [q|k|v] = (n * attention_in_multiplier) W_qkv ;  k <- k * key_multiplier
    o_j(t) = sum_{s <= t} softmax_s(R(q_j)(t) . R(k_{j // 5})(s) / sqrt(128)) v_{j // 5}(s)
    a      = [o_1..o_20] W_o * attention_out_multiplier
    h      = x + m + a
    g      = N_2(h)
    x     <- h + ((g W_up) * SiLU((g W_gate) * mlp_multipliers[0])) W_down * mlp_multipliers[1]
    logits = N_f(x) W_head * lm_head_multiplier

``R`` is rotary at ``rope_theta`` over all of a head's dimensions, paired
half-split (HF's rotate-half). **Departures from the published description**:
none in the equations; what the description leaves open is read as the
configuration file's ``assumed`` says (the order of ``ssm_multipliers``, no
clip on ``dt``, the gate before the grouped norm, the heads' groups). The cut
(nine layers, a slice of the vocabulary) is the caller's: ``layers`` yields
as many layers as are served and the head has as many columns as it has.

``control`` names a wrong model, for the checks that must tell it from the
right one: ``"bf16_state"`` (the state rounded to bfloat16 after every
token), ``"bf16_step"`` (the step's two terms rounded to bfloat16 before they
are added, and ``y`` after: a step accumulated in bfloat16),
``"no_ssm_out_multiplier"`` and ``"no_key_multiplier"`` (a dropped
multiplier), ``"state_not_carried"`` (the state starts at zero again at every
chunk of the prompt), ``"conv_history_dropped"`` (the convolution sees zeros
before a tick's first token), ``"conv_bias_dropped"``. The two that speak of
ticks read ``ticks = (prompt tokens, chunk)``. fp8 weights are the caller's
rounding of what it passes.
"""
from __future__ import annotations

import functools

import numpy as np

CONTROLS = (None, "bf16_state", "bf16_step", "no_ssm_out_multiplier",
            "no_key_multiplier", "state_not_carried", "conv_history_dropped",
            "conv_bias_dropped")

_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
         "mamba_n_groups", "mamba_d_conv", "rope_theta", "rms_norm_eps",
         "ssm_in_multiplier", "ssm_out_multiplier", "attention_in_multiplier",
         "attention_out_multiplier", "key_multiplier")


def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _static(config: dict) -> tuple:
    """The sizes and multipliers a layer's functions read, hashable: a
    compiled function a set of them."""
    return tuple((k, config[k]) for k in _KEYS) + (
        ("ssm_multipliers", tuple(config["ssm_multipliers"])),)


def _tick_start(s: int, ticks):
    """[s] int32: the first position of the tick that brought each one."""
    import jax.numpy as jnp

    pos = jnp.arange(s, dtype=jnp.int32)
    if ticks is None:
        return jnp.zeros((s,), jnp.int32)
    prompt, chunk = ticks
    return jnp.where(pos < prompt, pos // chunk * chunk, pos)


def _bf16(x):
    """Rounded to bfloat16's eight bits of mantissa (a cast there and back
    is excess precision to XLA, which drops the pair on the TPU)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.lru_cache(maxsize=None)
def _ssd_fn(cfg: tuple, control, ticks):
    import jax
    import jax.numpy as jnp

    c = dict(cfg)
    d_ssm, heads, p_dim, n_dim, groups, taps = (
        c["mamba_d_ssm"], c["mamba_n_heads"], c["mamba_d_head"],
        c["mamba_d_state"], c["mamba_n_groups"], c["mamba_d_conv"])
    eps, mup = c["rms_norm_eps"], c["ssm_multipliers"]
    in_mult, out_mult = c["ssm_in_multiplier"], c["ssm_out_multiplier"]
    bc = groups * n_dim

    def mixer(n, p, n_live):
        s = n.shape[0]
        pos = jnp.arange(s, dtype=jnp.int32)
        start = _tick_start(s, ticks)
        u = (n * in_mult) @ p["ssd.w_in.weight"]
        z = u[:, :d_ssm] * mup[0]
        xbc = jnp.concatenate([
            u[:, d_ssm:2 * d_ssm] * mup[1],
            u[:, 2 * d_ssm:2 * d_ssm + bc] * mup[2],
            u[:, 2 * d_ssm + bc:2 * d_ssm + 2 * bc] * mup[3]], -1)
        dt = jax.nn.softplus(u[:, 2 * d_ssm + 2 * bc:] * mup[4]
                             + p["ssd.dt_bias.weight"])          # [s, H]
        padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
        y = 0.0
        for j in range(taps):
            back = taps - 1 - j                 # the tap's distance back
            term = padded[j:j + s] * p["ssd.conv.weight"][j]
            if control == "conv_history_dropped":
                term = jnp.where((pos - back >= start)[:, None], term, 0.0)
            y = y + term
        if control != "conv_bias_dropped":
            y = y + p["ssd.conv_bias.weight"]
        act = jax.nn.silu(y)
        x = act[:, :d_ssm].reshape(s, heads, p_dim)
        of_head = lambda a: jnp.repeat(                     # noqa: E731
            a.reshape(s, groups, n_dim), heads // groups, axis=1)
        b, c = of_head(act[:, d_ssm:d_ssm + bc]), of_head(act[:, d_ssm + bc:])
        a_neg = -jnp.exp(p["ssd.A_log.weight"])
        reset = (pos == start) & (pos > 0) if control == "state_not_carried" \
            else jnp.zeros((s,), bool)

        def step(S, t):
            xt, bt, ct, dtt, live, zero = t
            S0 = jnp.where(zero, 0.0, S)
            keep = jnp.exp(dtt * a_neg)[:, None, None] * S0
            new = (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
            if control == "bf16_step":
                keep, new = _bf16(keep), _bf16(new)
            S1 = keep + new                                 # [H, P, N]
            if control == "bf16_state":
                S1 = _bf16(S1)
            yt = jnp.einsum("hpn,hn->hp", S1, ct)
            if control == "bf16_step":
                yt = _bf16(yt)
            return jnp.where(live, S1, S), yt

        S, y = jax.lax.scan(
            step, jnp.zeros((heads, p_dim, n_dim), jnp.float32),
            (x, b, c, dt, pos < n_live, reset))
        y = y + p["ssd.D.weight"][:, None] * x
        y = y.reshape(s, d_ssm) * jax.nn.silu(z)
        grp = y.reshape(s, groups, d_ssm // groups)
        grp = grp * jax.lax.rsqrt(jnp.mean(grp * grp, -1, keepdims=True)
                                  + eps)
        y = grp.reshape(s, d_ssm) * p["ssd.norm.weight"]
        out = y @ p["ssd.w_out.weight"]
        if control != "no_ssm_out_multiplier":
            out = out * out_mult
        # the three positions the convolution would look back on next
        last = jax.lax.dynamic_slice_in_dim(padded, n_live, taps - 1, 0)
        return out, S, last

    return jax.jit(mixer)


def _rope(x, theta: float):
    """Rotate-half rotary over ``x`` [s, heads, d] by position."""
    import jax.numpy as jnp

    s, d = x.shape[0], x.shape[-1]
    # (config.json writes theta as an integer past int32: a float here)
    inv = 1.0 / float(theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


@functools.lru_cache(maxsize=None)
def _attn_fn(cfg: tuple, control):
    import jax
    import jax.numpy as jnp

    c = dict(cfg)
    heads, kvh, hd, theta = (c["num_attention_heads"],
                             c["num_key_value_heads"], c["head_dim"],
                             c["rope_theta"])
    in_mult, out_mult, key_mult = (c["attention_in_multiplier"],
                                   c["attention_out_multiplier"],
                                   c["key_multiplier"])
    qw, kw = heads * hd, kvh * hd

    def attention(n, p):
        s = n.shape[0]
        qkv = (n * in_mult) @ p["attn.qkv.weight"]
        q = qkv[:, :qw].reshape(s, heads, hd)
        k = qkv[:, qw:qw + kw].reshape(s, kvh, hd)
        if control != "no_key_multiplier":
            k = k * key_mult
        v = qkv[:, qw + kw:].reshape(s, kvh, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        per = heads // kvh
        seen = jnp.tril(jnp.ones((s, s), bool))
        outs = []
        for j in range(kvh):    # a key/value head at a time: [per, s, s]
            att = jnp.einsum("tgd,sd->gts", q[:, j * per:(j + 1) * per],
                             k[:, j]) / np.sqrt(hd)
            w = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("gts,sd->tgd", w, v[:, j]))
        o = jnp.concatenate(outs, axis=1).reshape(s, qw)
        return (o @ p["attn.o.weight"]) * out_mult, k, v

    return jax.jit(attention)


#: columns of the SwiGLU's width a call takes: float32 of three whole
#: matrices of 5,120 x 21,504 is 1.3 GB, and at ``highest`` a product splits
#: each operand in three besides; a block's are 0.17 GB, and ``forward`` waits
#: for each block, so that the host never queues a layer's casts ahead of the
#: device (the check runs beside an engine that holds 13.6 of a chip's 16.9 GB)
_FFN_BLOCK = 2688


@functools.lru_cache(maxsize=None)
def _ffn_fns(eps: float, gate_mult: float, out_mult: float):
    import jax

    norm = jax.jit(lambda h, w: rms_norm(h, w, eps))
    block = jax.jit(lambda x, g, gate, up, down: x + (
        ((g @ up) * jax.nn.silu((g @ gate) * gate_mult)) @ down) * out_mult)
    return norm, block


_SSD, _ATTN = "ssd.", "attn."


def forward(layers, other: dict, tokens, config: dict, n_live=None,
            control=None, ticks=None) -> dict:
    """The full causal forward over ``tokens`` [s]. ``layers`` yields one
    layer's weights at a time by the names the program gives them (a mapping
    that may fetch a name when asked); ``other`` holds the embedding, the
    final norm and the head; ``config`` the sizes and multipliers under the
    keys of ``config.json``. The first ``n_live`` positions (default: all)
    move the states: a caller that pads ``tokens`` passes the true length.
    Returns float32 ``state`` [s, h] (what the head reads); ``states``, one
    ``[heads, P, N]`` a layer, the SSD state after position ``n_live - 1``;
    ``history``, one ``[taps - 1, C]`` a layer, the ``[x | B | C]``
    projections of the last ``taps - 1`` live positions (what the
    convolution looks back on next); and ``keys``, ``values``, each one ``[s,
    KVH, D]`` a layer, the rotated keys and the values as a cache would hold
    them."""
    import jax

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    tokens = np.asarray(tokens).reshape(-1)
    n_live = len(tokens) if n_live is None else int(n_live)
    cfg = _static(config)
    eps = config["rms_norm_eps"]
    ticks = None if ticks is None else tuple(int(t) for t in ticks)
    norm, ffn_block = _ffn_fns(eps, *config["mlp_multipliers"])
    out = {"states": [], "history": [], "keys": [], "values": []}
    with jax.default_matmul_precision("highest"):
        x = _f32(other["embeddings.wte.weight"][tokens]) \
            * config["embedding_multiplier"]
        for w in layers:
            n = norm(x, _f32(w["ln_1.weight"]))
            p = {k: _f32(w[k]) for k in w if k.startswith(_SSD)}
            m, S, last = _ssd_fn(cfg, control, ticks)(n, p, np.int32(n_live))
            p = {k: _f32(w[k]) for k in w if k.startswith(_ATTN)}
            a, k, v = _attn_fn(cfg, control)(n, p)
            del p
            out["states"].append(S)
            out["history"].append(last)
            out["keys"].append(k)
            out["values"].append(v)
            x = x + m + a
            g = norm(x, _f32(w["ln_2.weight"]))
            gate, up, down = (w["ffn.fc_gate.weight"], w["ffn.fc_in.weight"],
                              w["ffn.fc_out.weight"])
            for lo in range(0, gate.shape[1], _FFN_BLOCK):
                cols = slice(lo, lo + _FFN_BLOCK)
                x = ffn_block(x, g, _f32(gate[:, cols]), _f32(up[:, cols]),
                              _f32(down[cols])).block_until_ready()
        out["state"] = norm(x, _f32(other["ln_f.weight"]))
    return out


#: columns of the head a product takes at a time: float32 of the whole head
#: need not stand beside an engine's pools
_HEAD_BLOCK = 8192


def _head_blocks(state, other: dict, config: dict):
    """``state`` [n, h] float32 times the head and ``lm_head_multiplier``,
    ``_HEAD_BLOCK`` columns at a time: ``(first column, [n, block] float32
    logits)``."""
    import jax

    head = other["lm_head.weight"]
    with jax.default_matmul_precision("highest"):
        for lo in range(0, head.shape[1], _HEAD_BLOCK):
            yield lo, (_f32(state) @ _f32(head[:, lo:lo + _HEAD_BLOCK])) \
                * config["lm_head_multiplier"]


def logits(state, other: dict, config: dict):
    """``[s, vocab]`` float32 logits of ``forward``'s ``state``."""
    return np.concatenate([np.asarray(b) for _, b in _head_blocks(
        state, other, config)], -1)


def shortfall(state, other: dict, config: dict, targets):
    """For each position of ``state`` [n, h]: how far its logit for
    ``targets`` [n] lies below its largest logit, that logit itself, and the
    standard deviation of the position's logits over the vocabulary (the
    unit a seeded model's distances are read in: its logits are a few
    hundredths wide); each ``[n]`` float32 on the host."""
    targets = np.asarray(targets)
    top = np.full(targets.shape, -np.inf, np.float32)
    mine = np.zeros(targets.shape, np.float32)
    total = np.zeros(targets.shape, np.float64)
    squares = np.zeros(targets.shape, np.float64)
    width = 0
    for lo, block in _head_blocks(state, other, config):
        block = np.asarray(block)
        top = np.maximum(top, block.max(-1))
        here = (targets >= lo) & (targets < lo + block.shape[1])
        mine[here] = block[here, targets[here] - lo]
        total += block.sum(-1, dtype=np.float64)
        squares += np.square(block, dtype=np.float64).sum(-1)
        width += block.shape[1]
    sigma = np.sqrt(np.maximum(squares / width - (total / width) ** 2, 0.0))
    return top - mine, mine, sigma.astype(np.float32)
