"""The plain reference of Ling-3.0-flash (huggingface.co/inclusionAI/
Ling-3.0-flash config.json, ``model_type`` ``bailing_hybrid``): Kimi Delta
Attention layers (arXiv:2510.26692) beside latent attention (MLA,
arXiv:2405.04434 section 2.1) in periods of six, and a sigmoid router
limited to a few groups of experts (``noaux_tc``), of which a share is held.
Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: one full
causal forward over prompt and output together, the delta rule **token by
token**, the attention in the expanded (unabsorbed) form under a mask,
``top_k`` by sort, no chunking, no cache, no kernel, no batching. It takes
the weights the system holds (under the names ``models/ling3.py`` gives
them), a layer at a time and the held experts an expert at a time, and never
its code. ``perfbench/references/ling3.py`` is a copy.

With ``N(.)`` an RMSNorm with its own weight (eps 1e-6), every matrix
without bias, ``h = N1(x)`` the normed layer input::

    KDA layer (32 heads, keys and values of 128; [q~|k~|v~] = h W_qkv):
        [q~|k~|v~] <- SiLU(conv4([q~|k~|v~]))       # depthwise, causal, 4 taps
        q_i = q~_i / sqrt(|q~_i|^2 + 1e-6) ; k_i likewise ; v_i = v~_i
        g_i = -5 sigmoid(exp(A_log_i) ((h W_f)_i + dt_bias_i))   # a channel,
                                         # in (-5, 0): kda_safe_gate, lower bound -5
        beta_i = sigmoid((h W_b)_i)                 # a head
        S_i(t) = (I - beta_i k_i k_i^T) Diag(exp g_i) S_i(t-1) + beta_i k_i v_i^T
        o_i = S_i(t)^T q_i / sqrt(128)              # S_i(-1) = 0
        x <- x + [N_128(o_i) * sigmoid((h W_g)_i)]_i W_o

    MLA layer (32 heads of nope 128 + rope 64, v 128, kv_lora_rank 512):
        [q_nope|q_pe]_i = h W_q ; q_pe = rope(q_pe)            # theta 6e6
        [c_kv | k_pe]   = h W_kva ; c_kv = N(c_kv) ; k_pe = rope(k_pe)
        [k_nope | v]_i  = c_kv W_kvb            # the system caches (c_kv, k_pe)
        o_i(t) = sum_{s <= t} softmax_s((q_nope_i(t) . k_nope_i(s)
                                        + q_pe_i(t) . k_pe(s)) / sqrt(192)) v_i(s)
        x <- x + [sigmoid((h W_gate)_i) o_i]_i W_o             # a head's gate

    FFN, y = N2(x): the leading dense layers x <- x + W_down(silu(W_gate y) *
    W_up y); after them s = sigmoid(y W_r) over the router's 512; the
    experts are n_group 8 runs, a group's score the sum of its two largest
    s + expert_bias; outside the topk_group 4 best groups an expert cannot
    be chosen; the num_experts_per_tok 8 largest s + expert_bias are;
    weights s_e / sum_chosen s * routed_scaling_factor 2.5;
    x <- x + sum over the chosen experts that are held of weight_e
    SwiGLU_e(y) + SwiGLU_shared(y). What the absent experts would add is
    left out, as in the system.

    logits = N_f(x) W_head

``control`` names a wrong model, for the checks that must tell it from the
right one: ``"bf16_state"`` (the state rounded to bfloat16 after every
token), ``"unbounded_decay"`` (``g = -exp(A_log) softplus(.)``, Gated
DeltaNet's), ``"head_decay"`` (a head's decay the mean over its channels),
``"conv_history_dropped"`` (the convolution sees zeros before a tick's
first token: every chunk of the prompt, every decoded token; reads ``ticks =
(prompt tokens, chunk)``), ``"no_group_limit"`` (plain top-k over all
experts), ``"no_expert_bias"``, ``"not_renormalised"`` (weights ``s_e *
2.5``), ``"no_routed_scaling"`` (weights times 1), ``"no_rope"`` (nothing
rotated in the MLA layers), ``"no_head_gate"``. fp8 weights are the caller's
rounding of what it passes.
"""
from __future__ import annotations

import functools

import numpy as np

CONTROLS = (None, "bf16_state", "unbounded_decay", "head_decay",
            "conv_history_dropped", "no_group_limit", "no_expert_bias",
            "not_renormalised", "no_routed_scaling", "no_rope",
            "no_head_gate")


def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _static(config: dict) -> tuple:
    """The configuration's numbers as a hashable thing."""
    return tuple((k, v) for k, v in sorted(config.items())
                 if isinstance(v, (int, float, str, bool)) or v is None)


def _rows(fn, n: int, block: int, *xs):
    """``fn`` over row blocks of ``xs`` (each ``[n, ...]``, ``n`` a multiple
    of ``block``), so that no intermediate is ever ``n`` rows tall."""
    import jax

    cut = [x.reshape((n // block, block) + x.shape[1:]) for x in xs]
    out = jax.lax.map(lambda a: fn(*a), tuple(cut))
    return jax.tree.map(lambda y: y.reshape((n,) + y.shape[2:]), out)


def _block_of(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is at most ``most``."""
    return max(b for b in range(1, min(n, most) + 1) if n % b == 0)


def _tick_start(s: int, ticks):
    """[s] int32: the first position of the tick that brought each one."""
    import jax.numpy as jnp

    pos = jnp.arange(s, dtype=jnp.int32)
    if ticks is None:
        return jnp.zeros((s,), jnp.int32)
    prompt, chunk = ticks
    return jnp.where(pos < prompt, pos // chunk * chunk, pos)


@functools.lru_cache(maxsize=None)
def _kda_fn(cfg: tuple, control, ticks):
    """One KDA layer's mixer over ``x`` [s, h], jitted: the new ``x`` and
    the heads' states after position ``n_live - 1``."""
    import jax
    import jax.numpy as jnp

    c = dict(cfg)
    heads, d, taps = c["num_attention_heads"], c["head_dim"], \
        c["short_conv_kernel_size"]
    eps, low = c["rms_norm_eps"], float(c["kda_lower_bound"])
    kw = heads * d

    def mixer(x, p, n_live):
        s = x.shape[0]
        pos = jnp.arange(s, dtype=jnp.int32)
        start = _tick_start(s, ticks)
        h = rms_norm(x, p["ln_1.weight"], eps)
        qkv = h @ p["mix.qkv.weight"]
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
        q = l2(y[:, :kw].reshape(s, heads, d))
        k = l2(y[:, kw:2 * kw].reshape(s, heads, d))
        v = y[:, 2 * kw:].reshape(s, heads, d)
        f = (h @ p["mix.f.weight"] + p["mix.dt_bias.weight"]).reshape(
            s, heads, d)
        a = jnp.exp(p["mix.A_log.weight"])[None, :, None]
        g = low * jax.nn.sigmoid(a * f)
        if control == "unbounded_decay":
            g = -a * jax.nn.softplus(f)
        if control == "head_decay":
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        beta = jax.nn.sigmoid(h @ p["mix.b.weight"])

        def step(S, t):
            qt, kt, vt, gt, bt, live = t
            S1 = jnp.exp(gt)[:, :, None] * S
            S1 = S1 + (bt[:, None] * kt)[:, :, None] * (
                vt - jnp.einsum("hk,hkv->hv", kt, S1))[:, None, :]
            if control == "bf16_state":     # (a cast there and back is
                # excess precision to XLA, which drops the pair on the TPU)
                S1 = jax.lax.reduce_precision(S1, exponent_bits=8,
                                              mantissa_bits=7)
            o = jnp.einsum("hk,hkv->hv", qt, S1) * d ** -0.5
            return jnp.where(live, S1, S), o

        S, o = jax.lax.scan(step, jnp.zeros((heads, d, d), jnp.float32),
                            (q, k, v, g, beta, pos < n_live))
        o = rms_norm(o, p["mix.o_norm.weight"], eps)
        gate = jax.nn.sigmoid(h @ p["mix.gate.weight"]).reshape(s, heads, d)
        return x + (o * gate).reshape(s, -1) @ p["mix.o.weight"], S

    return jax.jit(mixer)


def rope(x, pos, theta: float):
    """``x`` [s, ..., d] rotated by ``pos`` [s] in the rotate-half
    convention: the angle of pair ``(j, j + d/2)`` is ``pos theta^(-2j/d)``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


@functools.lru_cache(maxsize=None)
def _mla_fn(cfg: tuple, control):
    """One MLA layer's mixer over ``x`` [s, h], jitted: the new ``x`` and,
    ``[s, heads]``, what is every position's head's own of its latent output
    ``sum_s w(s) c_kv(s)`` (the heads' mean taken out) under an alternating
    sign over the latent's channels, times the head's gate."""
    import jax
    import jax.numpy as jnp

    c = dict(cfg)
    nh, nope, rd, vd = c["num_attention_heads"], c["qk_nope_head_dim"], \
        c["qk_rope_head_dim"], c["v_head_dim"]
    rank, eps, hidden = c["kv_lora_rank"], c["rms_norm_eps"], \
        c["hidden_size"]
    theta, scale = float(c["rope_theta"]), (nope + rd) ** -0.5
    turn = (lambda a, pos: a) if control == "no_rope" \
        else (lambda a, pos: rope(a, pos, theta))

    def attention(x, p):
        s = x.shape[0]
        pos = jnp.arange(s, dtype=jnp.int32)
        h = rms_norm(x, p["ln_1.weight"], eps)
        kv = h @ p["attn.kv_a.weight"]
        c_kv = rms_norm(kv[:, :rank], p["attn.kv_a_norm.weight"], eps)
        k_pe = turn(kv[:, rank:], pos)
        gate = jnp.ones((s, nh)) if control == "no_head_gate" \
            else jax.nn.sigmoid(h @ p["attn.gate.weight"])
        sign = 1.0 - 2.0 * (jnp.arange(rank) % 2)
        w_kvb = p["attn.kv_b.weight"].reshape(rank, nh, nope + vd)
        w_q = p["attn.q.weight"].reshape(hidden, nh, nope + rd)
        w_o = p["attn.o.weight"].reshape(nh, vd, hidden)
        hg = _block_of(nh, 8)               # heads at a time
        qb = _block_of(s, 128)

        def heads(acc, g):
            wq = jax.lax.dynamic_slice_in_dim(w_q, g * hg, hg, 1)
            wkv = jax.lax.dynamic_slice_in_dim(w_kvb, g * hg, hg, 1)
            gt = jax.lax.dynamic_slice_in_dim(gate, g * hg, hg, 1)
            knv = jnp.einsum("sc,cnd->snd", c_kv, wkv)
            k = jnp.concatenate([
                knv[..., :nope],
                jnp.broadcast_to(k_pe[:, None], (s, hg, rd))], -1)
            v = knv[..., nope:]

            def queries(h_b, pos_b):
                q = jnp.einsum("th,hnd->tnd", h_b, wq)
                q = jnp.concatenate([q[..., :nope],
                                     turn(q[..., nope:], pos_b)], -1)
                sc = jnp.einsum("tnd,snd->tns", q, k) * scale
                seen = pos[None, :] <= pos_b[:, None]
                sc = jnp.where(seen[:, None, :], sc, -jnp.inf)
                w = jax.nn.softmax(sc, -1)
                # beside the head's output, its weights over the latents
                # themselves under an alternating sign: [t, hg]
                return jnp.einsum("tns,snd->tnd", w, v), \
                    jnp.einsum("tns,s->tn", w, c_kv @ sign)

            o, lat = _rows(queries, s, qb, h, pos)
            o = o * gt[..., None]                               # [s, hg, vd]
            wo = jax.lax.dynamic_slice_in_dim(w_o, g * hg, hg, 0)
            return acc + jnp.einsum("snd,ndh->sh", o, wo), lat

        y, lat = jax.lax.scan(heads, jnp.zeros_like(x),
                              jnp.arange(nh // hg))
        lat = jnp.swapaxes(lat, 0, 1).reshape(s, nh)
        # [s, heads]: what is a head's own of its latent output (the heads'
        # mean, the plain mean of the values, taken out), times its gate
        return x + y, gate * (lat - jnp.mean(lat, 1, keepdims=True))

    return jax.jit(attention)


@functools.lru_cache(maxsize=None)
def _ffn_fn(moe: bool, cfg: tuple, held: tuple, control):
    import jax
    import jax.numpy as jnp

    c = dict(cfg)
    eps = c["rms_norm_eps"]
    first, count = held

    def swiglu(y, w_gate, w_up, w_down):
        return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down

    def dense(x, p):
        def rows(xb):
            y = rms_norm(xb, p["ln_2.weight"], eps)
            return xb + swiglu(y, p["ffn.fc_gate.weight"],
                               p["ffn.fc_in.weight"],
                               p["ffn.fc_out.weight"])

        return _rows(rows, x.shape[0], _block_of(x.shape[0], 2048), x)

    def experts(x, p):
        top, groups, keep = c["num_experts_per_tok"], c["n_group"], \
            c["topk_group"]
        e = p["ffn.gate"].shape[1]                  # the router's width
        scaling = 1.0 if control == "no_routed_scaling" \
            else c["routed_scaling_factor"]
        shared = tuple(p["ffn.shared_" + k] for k in ("gate", "up", "down"))
        bias = jnp.zeros((e,)) if control == "no_expert_bias" \
            else p["ffn.select_bias"]

        def rows(xb):
            y = rms_norm(xb, p["ln_2.weight"], eps)
            score = jax.nn.sigmoid(y @ p["ffn.gate"])            # [t, E]
            biased = score + bias
            if control != "no_group_limit" and groups > 1:
                best = jax.lax.top_k(
                    biased.reshape(-1, groups, e // groups), 2)[0].sum(-1)
                _, kept = jax.lax.top_k(best, keep)              # [t, keep]
                inside = jnp.any(
                    kept[:, :, None] == jnp.arange(groups)[None, None, :], 1)
                biased = jnp.where(jnp.repeat(inside, e // groups, axis=1),
                                   biased, -jnp.inf)
            _, chosen = jax.lax.top_k(biased, top)
            picked = jnp.take_along_axis(score, chosen, -1)
            weight = picked * scaling
            if control != "not_renormalised":
                weight = weight / (picked.sum(-1, keepdims=True) + 1e-20)
            # each token's weight for every held expert, 0 where unchosen
            local = chosen - first                               # [t, top]
            mine = jnp.sum(jnp.where(
                local[:, :, None] == jnp.arange(count)[None, None, :],
                weight[:, :, None], 0.0), 1)                     # [t, count]

            def one(acc, ex):
                w_e, (wg, wu, wd) = ex
                return acc + w_e[:, None] * swiglu(
                    y, _f32(wg), _f32(wu), _f32(wd)), None

            routed, _ = jax.lax.scan(
                one, jnp.zeros_like(y),
                (mine.T, (p["ffn.w_gate"], p["ffn.w_up"], p["ffn.w_down"])))
            return xb + routed + swiglu(y, *shared), chosen.astype(jnp.int32)

        return _rows(rows, x.shape[0], _block_of(x.shape[0], 1024), x)

    return jax.jit(experts if moe else lambda x, p: (dense(x, p), None))


#: the held experts' stacks stay in the type they were given and are cast an
#: expert at a time: a layer's 755 M in float32 need not stand beside the rest
_KEPT = ("ffn.w_gate", "ffn.w_up", "ffn.w_down")
_FFN = ("ffn.", "ln_2.")


def forward(layers, other: dict, tokens, config: dict, held=(0, None),
            n_live=None, control=None, ticks=None) -> dict:
    """The full causal forward over ``tokens`` [s]. ``layers`` yields one
    ``(kind, moe, weights)`` a layer: ``"kda"`` or ``"mla"``, whether its
    FFN is the mixture, and its weights by the names the program gives them;
    ``other`` holds the embedding, the final norm and the head; ``config``
    the sizes under the keys of ``config.json``; ``held = (first, count)``
    the experts held (the router's width is its matrix's). The first
    ``n_live`` positions (default: all) move the recurrent states: a caller
    that pads ``tokens`` passes the true length. Returns float32 ``state``
    [s, h] (what the head reads), ``states``, one ``[heads, dk, dv]``
    float32 array a KDA layer (its state after position ``n_live - 1``),
    ``routed``, one ``[s, num_experts_per_tok]`` int32 array an expert layer
    (the experts each position chose), ``mla_out``, one ``[s, heads]``
    float32 array an MLA layer (``_mla_fn``'s second result), and
    ``held_first``."""
    import jax

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    tokens = np.asarray(tokens).reshape(-1)
    n_live = len(tokens) if n_live is None else int(n_live)
    cfg = _static(config)
    ticks = None if ticks is None else tuple(int(t) for t in ticks)
    states, routed, said = [], [], []
    with jax.default_matmul_precision("highest"):
        x = _f32(other["embeddings.wte.weight"][tokens])
        for kind, moe, w in layers:
            # a half's weights at a time: ``w`` may fetch a name when asked
            p = {k: _f32(w[k]) for k in w if not k.startswith(_FFN)}
            if kind == "mla":
                x, out = _mla_fn(cfg, control)(x, p)
                said.append(out)
            else:
                x, S = _kda_fn(cfg, control, ticks)(x, p, np.int32(n_live))
                states.append(S)
            p = {k: w[k] if k in _KEPT else _f32(w[k])
                 for k in w if k.startswith(_FFN)}
            count = p["ffn.w_gate"].shape[0] if moe else 0
            share = (held[0], held[1] if held[1] is not None else count)
            x, chosen = _ffn_fn(moe, cfg, share, control)(x, p)
            if chosen is not None:
                routed.append(chosen)
            del p
        state = jax.jit(rms_norm, static_argnums=2)(
            x, _f32(other["ln_f.weight"]), config["rms_norm_eps"])
    return {"state": state, "states": states, "routed": routed,
            "mla_out": said, "held_first": held[0]}


#: columns of the head a product takes at a time: float32 of the whole head
#: need not stand beside an engine's pools
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
