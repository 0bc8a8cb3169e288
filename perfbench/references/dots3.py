"""The plain reference of the language model of dots3-note-prev
(huggingface.co/dots-studio/dots3-note-prev config.json, ``model_type``
``dots3_note``): latent attention (MLA, arXiv:2405.04434 section 2.1) with
a learned sparse indexer in the full layers (DeepSeek-V3.2-Exp's lightning
indexer), windowed latent attention of its own widths in the sliding
layers, a headwise sigmoid gate on both, and a sigmoid-routed mixture of
experts (arXiv:2412.19437 section 2.1.2, ``noaux_tc``) of which a share is
held. Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: one
full causal forward over prompt and output together, no cache, no kernel,
no batching, the selection a plain ``top_k`` over exact scores, the
attention in the expanded form (per-head keys and values made from the
latents) under a mask. It takes the weights the system holds (under the
names ``models/dots3.py`` gives them) and never its code.
This is the benchmark's copy of
``paddle_tpu/models/dots3_reference.py``.

With ``N(.)`` an RMSNorm with its own weight (eps 1e-5), ``h = N1(x)`` the
normed layer input and ``rope`` the rotate-half rotation by position::

    full layer (kv_lora_rank 512, q_lora_rank 1,024, 128 heads of nope 128
    + rope 64, v 128, rope_theta 8e7):
        c_q            = r_q N(h W_qa)                    r_q = sqrt(hidden / q_lora_rank)
        [q_nope|q_rope]_i = c_q W_qb ; q_rope = rope(q_rope)
        [c_kv | k_rope]   = h W_kva ; c_kv = r_kv N(c_kv) ; k_rope = rope(k_rope)
                                                          r_kv = sqrt(hidden / kv_lora_rank)
        [k_nope | v]_i    = c_kv W_kvb                    # the system caches (c_kv, k_rope)
        indexer: qI_j = c_q W_qI (64 heads of 128, rope on the first 64)
                 kI   = LayerNorm(h W_kI) (128, rope on the first 64)   # cached
                 w    = h W_w / sqrt(64) / sqrt(128)
                 I(t,s) = sum_j w_j(t) relu(qI_j(t) . kI(s))
                 S_t  = the index_topk largest I(t,s) over s <= t (all while t < index_topk)
        o_i(t) = sum_{s in S_t} softmax_s(q_i(t) . k_i(s) / sqrt(192)) v_i(s)
        o_i   <- sigmoid(h W_g)_i o_i ;  x <- x + [o_1..o_128] W_o

    sliding layer (64 heads, latents 1,024 / 1,024, nope 192 + rope 64,
    v 128, swa_rope_theta 5e4): the same at those widths, no indexer, keys
    t - window < s <= t, scale 1 / sqrt(256)

    FFN: layer 0 (first_k_dense_replace 1) x <- x + W_down(silu(W_gate N2(x)) * W_up N2(x));
    after it s = sigmoid(N2(x) W_r) (n_routed_experts wide); the
    num_experts_per_tok largest of s + b chosen (b selects only), weights
    s_e / sum_chosen s times routed_scaling_factor; x <- x + sum over the
    chosen experts that are held of weight_e SwiGLU_e(N2(x)) + SwiGLU_shared(N2(x)).
    What the absent experts would add is left out, as in the system.

    logits = N_f(x) W_head

``control`` names a wrong model, for the checks that must tell it from the
right one: ``"recent_topk"`` (the last ``index_topk`` positions in place of
the indexer's), ``"window_all"`` (sliding layers see every earlier
position), ``"no_gate"`` (no output gate), ``"unscaled_latent"`` (``r_q =
r_kv = 1``), ``"other_share"`` (the held weights taken for the experts
after the held ones), ``"no_select_bias"`` (``b = 0``). fp8 weights are the
caller's rounding of what it passes.
"""
from __future__ import annotations

import functools
import math

import numpy as np

CONTROLS = (None, "recent_topk", "window_all", "no_gate", "unscaled_latent",
            "other_share", "no_select_bias")


def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    import jax
    import jax.numpy as jnp

    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * w + b


def rope(x, pos, theta):
    """``x`` [s, ..., d] rotated by ``pos`` [s] in the rotate-half
    convention: the angle of pair ``(i, i + d/2)`` is ``pos / theta**(2i/d)``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
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


def _widths(c: dict, kind: str) -> dict:
    pre = "swa_" if kind == "sliding_attention" else ""
    return {"heads": c[pre + "num_attention_heads"],
            "q_rank": c[pre + "q_lora_rank"],
            "kv_rank": c[pre + "kv_lora_rank"],
            "nope": c[pre + "qk_nope_head_dim"],
            "rope": c[pre + "qk_rope_head_dim"], "v": c[pre + "v_head_dim"],
            "theta": float(c[pre + "rope_theta"])}


@functools.lru_cache(maxsize=None)
def _attention_fn(kind: str, cfg: tuple, control):
    """One layer's attention half over ``x`` [s, h], jitted: returns the new
    ``x``, for a full layer the selected positions ``[s, topk]`` (-1 where
    fewer are visible), and ``[s]`` the log of the sum of each position's
    exponentiated scores, mean over the heads."""
    import jax
    import jax.numpy as jnp

    c = dict(cfg)
    w = _widths(c, kind)
    nh, nope, rd, vd = w["heads"], w["nope"], w["rope"], w["v"]
    eps, hidden = c["rms_norm_eps"], c["hidden_size"]
    full = kind == "full_attention"
    rescale = c["apply_mla_qkv_lora_rescale"] and control != "unscaled_latent"
    r_q = math.sqrt(hidden / w["q_rank"]) if rescale else 1.0
    r_kv = math.sqrt(hidden / w["kv_rank"]) if rescale else 1.0
    window = c["sliding_window_size"]
    topk = c["index_topk"]

    def attention(x, p):
        s = x.shape[0]
        pos = jnp.arange(s, dtype=jnp.int32)

        def per_token(xb, pos_b):
            """What is small enough to keep for every position."""
            h = rms_norm(xb, p["ln_1.weight"], eps)
            c_q = r_q * rms_norm(h @ p["attn.q_a.weight"],
                                 p["attn.q_a_norm.weight"], eps)
            kv = h @ p["attn.kv_a.weight"]
            c_kv = r_kv * rms_norm(kv[:, :w["kv_rank"]],
                                   p["attn.kv_a_norm.weight"], eps)
            k_rope = rope(kv[:, w["kv_rank"]:], pos_b, w["theta"])
            gate = jnp.ones((xb.shape[0], nh), jnp.float32) \
                if control == "no_gate" \
                else jax.nn.sigmoid(h @ p["attn.gate.weight"])
            out = (c_q, c_kv, k_rope, gate)
            if full:
                out += _index_keys(c, p, h, pos_b, w["theta"])
            return out

        c_q, c_kv, k_rope, gate, *index = _rows(
            per_token, s, _block_of(s, 1024), x, pos)
        selected = _select(c, p, c_q, *index, pos, w["theta"], topk,
                           control) if full else None
        w_kvb = p["attn.kv_b.weight"].reshape(w["kv_rank"], nh, nope + vd)
        w_qb = p["attn.q_b.weight"].reshape(w["q_rank"], nh, nope + rd)
        w_o = p["attn.o.weight"].reshape(nh, vd, hidden)
        scale = 1.0 / math.sqrt(nope + rd)
        hg = _block_of(nh, 8)               # heads at a time
        qb = _block_of(s, 128 if full else 256)

        def heads(acc, g):
            wq = jax.lax.dynamic_slice_in_dim(w_qb, g * hg, hg, 1)
            wkv = jax.lax.dynamic_slice_in_dim(w_kvb, g * hg, hg, 1)
            knv = jnp.einsum("sc,cnd->snd", c_kv, wkv)
            k = jnp.concatenate([
                knv[..., :nope],
                jnp.broadcast_to(k_rope[:, None], (s, hg, rd))], -1)
            v = knv[..., nope:]

            def queries(cq_b, pos_b, sel_b):
                q = jnp.einsum("tc,cnd->tnd", cq_b, wq)
                q = jnp.concatenate([
                    q[..., :nope], rope(q[..., nope:], pos_b, w["theta"])],
                    -1)
                sc = jnp.einsum("tnd,snd->tns", q, k) * scale
                seen = pos[None, :] <= pos_b[:, None]
                if full:
                    mine = jnp.zeros((qb, s + 1), bool).at[
                        jnp.arange(qb)[:, None],
                        jnp.where(sel_b < 0, s, sel_b)].set(True)[:, :s]
                    seen = seen & mine
                elif control != "window_all":
                    seen = seen & (pos[None, :] > pos_b[:, None] - window)
                sc = jnp.where(seen[:, None, :], sc, -jnp.inf)
                pr = jax.nn.softmax(sc, -1)
                return (jnp.einsum("tns,snd->tnd", pr, v),
                        jnp.sum(jax.nn.logsumexp(sc, -1), -1))

            sel = selected if full else jnp.zeros((s, 1), jnp.int32)
            o, lse = _rows(queries, s, qb, c_q, pos, sel)    # [s, hg, vd]
            o = o * jax.lax.dynamic_slice_in_dim(gate, g * hg, hg,
                                                 1)[..., None]
            wo = jax.lax.dynamic_slice_in_dim(w_o, g * hg, hg, 0)
            return (acc[0] + jnp.einsum("snd,ndh->sh", o, wo),
                    acc[1] + lse), None

        (y, lse), _ = jax.lax.scan(
            heads, (jnp.zeros_like(x), jnp.zeros((s,), jnp.float32)),
            jnp.arange(nh // hg))
        return x + y, selected, lse / nh

    return jax.jit(attention)


def _index_keys(c, p, h, pos, theta):
    """The indexer's cached keys ``[s, index_head_dim]`` (rope on the first
    ``qk_rope_head_dim``) and each position's head weights ``[s, J]``."""
    import jax.numpy as jnp

    nj, dj, rd = c["index_n_heads"], c["index_head_dim"], \
        c["qk_rope_head_dim"]
    k_i = layer_norm(h @ p["attn.idx_k.weight"], p["attn.idx_k_norm.weight"],
                     p["attn.idx_k_norm.bias"], 1e-6)
    k_i = jnp.concatenate([rope(k_i[..., :rd], pos, theta), k_i[..., rd:]],
                          -1)
    return k_i, (h @ p["attn.idx_w.weight"]) / math.sqrt(nj) / math.sqrt(dj)


def _select(c, p, c_q, k_i, w_i, pos, theta, topk, control):
    """``[s, topk]`` int32: each position's selected earlier positions,
    -1 where fewer than ``topk`` are visible."""
    import jax
    import jax.numpy as jnp

    s = c_q.shape[0]
    k = min(topk, s)
    if control == "recent_topk":
        idx = pos[:, None] - jnp.arange(k, dtype=jnp.int32)[None, :]
        return jnp.where(idx >= 0, idx, -1)
    nj, dj, rd = c["index_n_heads"], c["index_head_dim"], \
        c["qk_rope_head_dim"]

    def queries(cq_b, w_b, pos_b):
        q_i = (cq_b @ p["attn.idx_q.weight"]).reshape(-1, nj, dj)
        q_i = jnp.concatenate([rope(q_i[..., :rd], pos_b, theta),
                               q_i[..., rd:]], -1)
        score = jnp.einsum("tj,tjs->ts", w_b, jax.nn.relu(
            jnp.einsum("tjd,sd->tjs", q_i, k_i)))
        score = jnp.where(pos[None, :] <= pos_b[:, None], score, -jnp.inf)
        val, idx = jax.lax.top_k(score, k)
        return jnp.where(val > -jnp.inf, idx, -1).astype(jnp.int32)

    return _rows(queries, s, _block_of(s, 64), c_q, w_i, pos)


@functools.lru_cache(maxsize=None)
def _ffn_fn(moe: bool, cfg: tuple, held: tuple, control):
    import jax
    import jax.numpy as jnp

    c = dict(cfg)
    eps = c["rms_norm_eps"]
    first, count = held
    if control == "other_share":
        first += count

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
        top = c["num_experts_per_tok"]
        bias = p["ffn.select_bias"]
        if control == "no_select_bias":
            bias = jnp.zeros_like(bias)

        def rows(xb):
            y = rms_norm(xb, p["ln_2.weight"], eps)
            score = jax.nn.sigmoid(y @ p["ffn.gate"])            # [t, E]
            _, chosen = jax.lax.top_k(score + bias, top)
            picked = jnp.take_along_axis(score, chosen, -1)
            weight = picked / picked.sum(-1, keepdims=True) \
                * c["routed_scaling_factor"]
            # each token's weight for every held expert, 0 where unchosen
            local = chosen - first                               # [t, top]
            mine = jnp.sum(jnp.where(
                local[:, :, None] == jnp.arange(count)[None, None, :],
                weight[:, :, None], 0.0), 1)                     # [t, count]

            def one(acc, e):
                w_e, (wg, wu, wd) = e
                return acc + w_e[:, None] * swiglu(
                    y, _f32(wg), _f32(wu), _f32(wd)), None

            routed, _ = jax.lax.scan(
                one, jnp.zeros_like(y),
                (mine.T, (p["ffn.w_gate"], p["ffn.w_up"], p["ffn.w_down"])))
            shared = swiglu(y, p["ffn.shared_gate"], p["ffn.shared_up"],
                            p["ffn.shared_down"])
            return xb + routed + shared, chosen.astype(jnp.int32)

        return _rows(rows, x.shape[0], _block_of(x.shape[0], 1024), x)

    return jax.jit(experts if moe else lambda x, p: (dense(x, p), None))


#: the held experts' stacks stay in the type they were given and are cast an
#: expert at a time: a layer's 755 M in float32 would not fit beside the rest
_KEPT = ("ffn.w_gate", "ffn.w_up", "ffn.w_down")
_FFN = ("ffn.", "ln_2.")


def forward(layers, other: dict, tokens, config: dict, held=(0, None),
            control=None) -> dict:
    """The full causal forward over ``tokens`` [s]. ``layers`` yields one
    ``(kind, moe, weights)`` a layer: its ``layer_types`` entry, whether
    its FFN is the mixture, and its weights by the names the program gives
    them without the stacking; ``other`` holds the embedding, the final
    norm and the head; ``config`` the sizes under the keys of
    ``config.json``; ``held = (first, count)`` the experts held. Returns
    float32 ``state`` [s, h] (what the head reads), ``selected``, one
    ``[s, index_topk]`` int32 array a full layer (-1: fewer visible),
    ``routed``, one ``[s, num_experts_per_tok]`` int32 array an expert
    layer (the experts each position chose), ``window_lse``, one ``[s]``
    array a sliding layer (the log of the sum of each position's
    exponentiated scores, mean over the heads) and ``held_first``, the
    first expert the held weights were taken for."""
    import jax

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    tokens = np.asarray(tokens).reshape(-1)
    cfg = tuple(sorted((k, v) for k, v in config.items()
                       if isinstance(v, (int, float, str, bool))))
    selected, routed, window_lse, first_used = [], [], [], held[0]
    with jax.default_matmul_precision("highest"):
        x = _f32(other["embeddings.wte.weight"])[tokens]
        for kind, moe, w in layers:
            # a half's weights at a time: ``w`` may fetch a name when asked
            x, sel, lse = _attention_fn(kind, cfg, control)(x, {
                k: _f32(w[k]) for k in w if not k.startswith(_FFN)})
            if sel is not None:
                selected.append(sel)
            else:
                window_lse.append(lse)
            p = {k: w[k] if k in _KEPT else _f32(w[k])
                 for k in w if k.startswith(_FFN)}
            count = p["ffn.w_gate"].shape[0] if moe else 0
            share = (held[0], held[1] if held[1] is not None else count)
            if moe:
                first_used = share[0] + (
                    share[1] if control == "other_share" else 0)
            x, chosen = _ffn_fn(moe, cfg, share, control)(x, p)
            if chosen is not None:
                routed.append(chosen)
            del p
        state = jax.jit(rms_norm, static_argnums=2)(
            x, _f32(other["ln_f.weight"]), config["rms_norm_eps"])
    return {"state": state, "selected": selected, "routed": routed,
            "window_lse": window_lse, "held_first": first_used}


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
