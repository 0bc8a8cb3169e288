"""The plain reference of DeepSeek-V2 (huggingface.co/deepseek-ai/DeepSeek-V2
config.json, ``model_type`` ``deepseek_v2``; arXiv:2405.04434): latent
attention (MLA, section 2.1) over the whole context in every layer under
YaRN, and DeepSeekMoE (section 2.2) with a softmax router limited to a few
groups of experts, of which a share is held. Straight ``jax.numpy`` in
float32 at ``highest`` matmul precision: one full causal forward over prompt
and output together, no cache, no kernel, no batching, the attention in the
expanded (unabsorbed) form under a mask, ``top_k`` by sort. It takes the
weights the system holds (under the names ``models/deepseek_v2.py`` gives
them) and never its code.
This is the benchmark's copy of
``paddle_tpu/models/deepseek_v2_reference.py``.

With ``N(.)`` an RMSNorm with its own weight (eps 1e-6), ``h = N1(x)`` the
normed layer input and ``rope`` the rotate-half rotation by position with
YaRN's frequencies::

    attention (every layer; 128 heads of nope 128 + rope 64, v 128,
    q_lora_rank 1,536, kv_lora_rank 512):
        c_q              = N(h W_qa)
        [q_nope|q_pe]_i  = c_q W_qb ; q_pe = rope(q_pe)
        [c_kv | k_pe]    = h W_kva ; c_kv = N(c_kv) ; k_pe = rope(k_pe)
        [k_nope | v]_i   = c_kv W_kvb              # the system caches (c_kv, k_pe)
        o_i(t) = sum_{s <= t} softmax_s((q_nope_i(t) . k_nope_i(s)
                                        + q_pe_i(t) . k_pe(s)) * scale) v_i(s)
        x <- x + [o_1..o_128] W_o

    YaRN (rope_scaling: factor 40, original 4,096, beta_fast 32, beta_slow 1,
    mscale = mscale_all_dim = 0.707; theta 1e4, d = 64):
        f_j = theta^(-2j/d) ; dim(n) = d ln(orig / (2 pi n)) / (2 ln theta)
        low = max(floor(dim(beta_fast)), 0) = 10 ; high = min(ceil(dim(beta_slow)), d - 1) = 23
        ramp_j = clip((j - low) / (high - low), 0, 1)
        inv_freq_j = f_j (1 - ramp_j) + (f_j / factor) ramp_j
        m(x) = 0.1 x ln(factor) + 1 ; cos, sin times m(mscale) / m(mscale_all_dim) = 1
        scale = (128 + 64)^-0.5 * m(mscale_all_dim)^2 = 0.11472

    FFN: layer 0 (first_k_dense_replace 1) x <- x + W_down(silu(W_gate N2(x)) * W_up N2(x));
    after it s = softmax(N2(x) W_r) over the n_routed_experts; the experts
    are n_group runs, a group's score its best expert's; s is set to 0
    outside the topk_group best groups and the num_experts_per_tok largest
    are chosen, weights s_e * routed_scaling_factor (not renormalised);
    x <- x + sum over the chosen experts that are held of weight_e
    SwiGLU_e(N2(x)) + SwiGLU_shared(N2(x)), the shared expert one SwiGLU of
    n_shared_experts * moe_intermediate_size. What the absent experts would
    add is left out, as in the system.

    logits = N_f(x) W_head

``control`` names a wrong model, for the checks that must tell it from the
right one: ``"no_group_limit"`` (plain top-k over all experts),
``"no_routed_scaling"`` (weights times 1), ``"renormalised"`` (weights
``s_e / sum_chosen s``), ``"no_yarn"`` (unscaled frequencies),
``"no_mscale"`` (``scale = (nope + rope)^-0.5``), ``"one_shared"`` (the
shared expert's first ``moe_intermediate_size`` columns alone). fp8 weights
are the caller's rounding of what it passes.
"""
from __future__ import annotations

import functools
import math

import numpy as np

CONTROLS = (None, "no_group_limit", "no_routed_scaling", "renormalised",
            "no_yarn", "no_mscale", "one_shared")


def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn(c: dict, control=None):
    """``(inv_freq [d / 2] float64, cos_sin_factor, softmax scale)`` from
    the configuration's ``rope_theta``, ``rope_scaling`` and head widths."""
    d, theta = c["qk_rope_head_dim"], float(c["rope_theta"])
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    plain = (c["qk_nope_head_dim"] + d) ** -0.5
    rs = c["rope_scaling"]
    if rs is None:
        return f, 1.0, plain
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]
    dim = lambda n: d * math.log(orig / (2 * math.pi * n)) \
        / (2 * math.log(theta))                             # noqa: E731
    low = max(math.floor(dim(rs["beta_fast"])), 0)
    high = min(math.ceil(dim(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = f if control == "no_yarn" else f * (1 - ramp) + f / factor * ramp
    m = lambda x: 0.1 * x * math.log(factor) + 1.0 if factor > 1 \
        else 1.0                                            # noqa: E731
    m_all = m(rs["mscale_all_dim"])
    return (inv, m(rs["mscale"]) / m_all,
            plain if control == "no_mscale" else plain * m_all * m_all)


def rope(x, pos, inv_freq, factor=1.0):
    """``x`` [s, ..., d] rotated by ``pos`` [s] in the rotate-half
    convention: the angle of pair ``(j, j + d/2)`` is ``pos * inv_freq[j]``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) \
        * jnp.asarray(inv_freq, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * factor
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * factor
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


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


def _static(config: dict) -> tuple:
    """The configuration's sizes as a hashable thing (the YaRN block by its
    sorted items)."""
    out = []
    for k, v in sorted(config.items()):
        if isinstance(v, dict) and k == "rope_scaling":
            out.append((k, tuple(sorted(v.items()))))
        elif isinstance(v, (int, float, str, bool)) or v is None:
            out.append((k, v))
    return tuple(out)


def _config(cfg: tuple) -> dict:
    c = dict(cfg)
    if isinstance(c.get("rope_scaling"), tuple):
        c["rope_scaling"] = dict(c["rope_scaling"])
    return c


@functools.lru_cache(maxsize=None)
def _attention_fn(cfg: tuple, control):
    """One layer's attention half over ``x`` [s, h], jitted: the new ``x``."""
    import jax
    import jax.numpy as jnp

    c = _config(cfg)
    nh, nope, rd, vd = c["num_attention_heads"], c["qk_nope_head_dim"], \
        c["qk_rope_head_dim"], c["v_head_dim"]
    rank, q_rank = c["kv_lora_rank"], c["q_lora_rank"]
    eps, hidden = c["rms_norm_eps"], c["hidden_size"]
    inv, factor, scale = yarn(c, control)

    def attention(x, p):
        s = x.shape[0]
        pos = jnp.arange(s, dtype=jnp.int32)

        def per_token(xb, pos_b):
            """What is small enough to keep for every position."""
            h = rms_norm(xb, p["ln_1.weight"], eps)
            c_q = rms_norm(h @ p["attn.q_a.weight"],
                           p["attn.q_a_norm.weight"], eps)
            kv = h @ p["attn.kv_a.weight"]
            c_kv = rms_norm(kv[:, :rank], p["attn.kv_a_norm.weight"], eps)
            return c_q, c_kv, rope(kv[:, rank:], pos_b, inv, factor)

        c_q, c_kv, k_pe = _rows(per_token, s, _block_of(s, 1024), x, pos)
        w_kvb = p["attn.kv_b.weight"].reshape(rank, nh, nope + vd)
        w_qb = p["attn.q_b.weight"].reshape(q_rank, nh, nope + rd)
        w_o = p["attn.o.weight"].reshape(nh, vd, hidden)
        hg = _block_of(nh, 8)               # heads at a time
        qb = _block_of(s, 128)

        def heads(acc, g):
            wq = jax.lax.dynamic_slice_in_dim(w_qb, g * hg, hg, 1)
            wkv = jax.lax.dynamic_slice_in_dim(w_kvb, g * hg, hg, 1)
            knv = jnp.einsum("sc,cnd->snd", c_kv, wkv)
            k = jnp.concatenate([
                knv[..., :nope],
                jnp.broadcast_to(k_pe[:, None], (s, hg, rd))], -1)
            v = knv[..., nope:]

            def queries(cq_b, pos_b):
                q = jnp.einsum("tc,cnd->tnd", cq_b, wq)
                q = jnp.concatenate([
                    q[..., :nope], rope(q[..., nope:], pos_b, inv, factor)],
                    -1)
                sc = jnp.einsum("tnd,snd->tns", q, k) * scale
                seen = pos[None, :] <= pos_b[:, None]
                sc = jnp.where(seen[:, None, :], sc, -jnp.inf)
                return jnp.einsum("tns,snd->tnd", jax.nn.softmax(sc, -1), v)

            o = _rows(queries, s, qb, c_q, pos)              # [s, hg, vd]
            wo = jax.lax.dynamic_slice_in_dim(w_o, g * hg, hg, 0)
            return acc + jnp.einsum("snd,ndh->sh", o, wo), None

        y, _ = jax.lax.scan(heads, jnp.zeros_like(x), jnp.arange(nh // hg))
        return x + y

    return jax.jit(attention)


@functools.lru_cache(maxsize=None)
def _ffn_fn(moe: bool, cfg: tuple, held: tuple, control):
    import jax
    import jax.numpy as jnp

    c = _config(cfg)
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
        top, e = c["num_experts_per_tok"], c["n_routed_experts"]
        groups, keep = c["n_group"], c["topk_group"]
        scaling = 1.0 if control in ("no_routed_scaling", "renormalised") \
            else c["routed_scaling_factor"]
        shared = tuple(p["ffn.shared_" + k] for k in ("gate", "up", "down"))
        if control == "one_shared":
            f = c["moe_intermediate_size"]
            shared = (shared[0][:, :f], shared[1][:, :f], shared[2][:f])

        def rows(xb):
            y = rms_norm(xb, p["ln_2.weight"], eps)
            score = jax.nn.softmax(y @ p["ffn.gate"], -1)        # [t, E]
            limited = score
            if control != "no_group_limit" and groups > 1:
                best = score.reshape(-1, groups, e // groups).max(-1)
                _, kept = jax.lax.top_k(best, keep)              # [t, keep]
                inside = jnp.any(
                    kept[:, :, None] == jnp.arange(groups)[None, None, :], 1)
                limited = jnp.where(jnp.repeat(inside, e // groups, axis=1),
                                    score, 0.0)
            picked, chosen = jax.lax.top_k(limited, top)
            weight = picked * scaling
            if control == "renormalised":
                weight = picked / (picked.sum(-1, keepdims=True) + 1e-20)
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
#: expert at a time: a layer's 472 M in float32 need not stand beside the rest
_KEPT = ("ffn.w_gate", "ffn.w_up", "ffn.w_down")
_FFN = ("ffn.", "ln_2.")


def forward(layers, other: dict, tokens, config: dict, held=(0, None),
            control=None) -> dict:
    """The full causal forward over ``tokens`` [s]. ``layers`` yields one
    ``(moe, weights)`` a layer: whether its FFN is the mixture, and its
    weights by the names the program gives them; ``other`` holds the
    embedding, the final norm and the head; ``config`` the sizes under the
    keys of ``config.json`` (``n_routed_experts`` the router's width);
    ``held = (first, count)`` the experts held. Returns float32 ``state``
    [s, h] (what the head reads), ``routed``, one ``[s,
    num_experts_per_tok]`` int32 array an expert layer (the experts each
    position chose), and ``held_first``."""
    import jax

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    tokens = np.asarray(tokens).reshape(-1)
    cfg = _static(config)
    routed = []
    with jax.default_matmul_precision("highest"):
        x = _f32(other["embeddings.wte.weight"])[tokens]
        for moe, w in layers:
            # a half's weights at a time: ``w`` may fetch a name when asked
            x = _attention_fn(cfg, control)(x, {
                k: _f32(w[k]) for k in w if not k.startswith(_FFN)})
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
    return {"state": state, "routed": routed, "held_first": held[0]}


def logits(state, other: dict):
    """``[s, vocab]`` float32 logits of ``forward``'s ``state``."""
    import jax

    with jax.default_matmul_precision("highest"):
        return state @ _f32(other["lm_head.weight"])


@functools.lru_cache(maxsize=None)
def _shortfall_fn():
    import jax
    import jax.numpy as jnp

    def f(state, head, targets):
        lg = state @ head
        mine = jnp.take_along_axis(lg, targets[:, None], -1)[:, 0]
        return lg.max(-1) - mine, mine

    return jax.jit(f)


def shortfall(state, other: dict, targets):
    """For each position of ``state`` [n, h]: how far its logit for
    ``targets`` [n] lies below its largest logit, and that logit itself;
    both ``[n]`` float32 on the host."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        short, mine = _shortfall_fn()(_f32(state),
                                      _f32(other["lm_head.weight"]),
                                      jnp.asarray(targets, jnp.int32))
    return np.asarray(short), np.asarray(mine)
