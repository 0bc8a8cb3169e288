"""The plain reference of Solar-Open2 (huggingface.co/upstage/Solar-Open2-250B
``config.json``; the linear-attention layers are Kimi Delta Attention,
arXiv:2510.26692 section 3 and ``fla.layers.kda.KimiDeltaAttention``).
Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: the
linear-attention recurrence **token by token** (``lax.scan`` over positions,
state ``[heads, 128, 128]``), softmax attention as a masked softmax with K
and V shared by the heads of their group, a Python loop over the experts with
boolean masks, no chunk, no sort, no kernel. It takes the weights the system
holds (under the names ``models/solar_open2.py`` gives them) and never its
code. ``perfbench/references/solar_open2.py`` is a copy.

Pre-norm residual layers, RMSNorm, no biases but one, no position embedding:
``x = x + Mix(norm x); x = x + MoE(norm x)``; ``Mix`` is grouped-query
softmax attention in the first layer of every period of four and KDA in the
other three. What the published description leaves open, and where this
departs from it, each for a stated reason:

1. **The softmax layer's gate** (``use_gqa_gate`` says only that there is
   one): elementwise, ``out = W_o [attn * sigmoid(W_gate x)]`` with
   ``W_gate`` as wide as the heads' output, the form of arXiv:2505.06708.
   With it the whole model counts 250.3 B parameters, the model's name.
2. **The low-rank widths** of the decay and of the output gate
   (``kda_use_full_proj`` false): 128, the head size, as fla's layer takes
   them. The decay's second projection has no bias but ``dt_bias``; the
   gate's second projection has one.
3. **The selection bias** ``b`` [320] is added to the sigmoid scores to
   choose the 8 experts and to nothing else (the convention of the lineage
   whose keys these are: ``n_routed_experts``, ``norm_topk_prob``,
   ``routed_scaling_factor``). It is seeded, takes no gradient and no
   update; its balancing rule is no part of a step, and no auxiliary loss
   is added.
4. **The experts held**: ``held = (first, count)`` computes the part of the
   expert layer that experts ``first : first + count`` give, weights
   renormalised over **all** 8 chosen, held or not, plus the shared expert;
   what the absent experts would add is left out, and that partial result
   goes on to the next layer (one rank's share of an expert-parallel
   layout).
5. ``l2norm`` adds 1e-6 under the root, as ``fla.modules.l2norm`` does.
6. The short convolution's last tap multiplies the token itself, with no
   bias and SiLU after it, as fla's ``ShortConvolution`` is used there.
"""
from __future__ import annotations

import functools

import numpy as np

L2_EPS = 1e-6
#: two selection scores closer than this share of their value are a near
#: tie: bf16 activations (2^-8) may order them either way
NEAR_TIE = 2.0 ** -7


def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def l2norm(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def short_conv(x, w):
    """Causal depthwise convolution, ``x`` [b, s, c], ``w`` [taps, c]:
    ``y_t = sum_j w[j] x_{t - (taps - 1 - j)}``, zeros before the start."""
    import jax.numpy as jnp

    taps, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + s] * w[j] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """The recurrence itself, one token a step. q, k, g [b, s, heads, dk],
    v [b, s, heads, dv], beta [b, s, heads]; the state starts at 0::

        S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t / sqrt(dk)

    The steps run in segments of up to 64 under ``jax.checkpoint``: the
    same steps in the same order, but ``jax.vjp`` keeps one state a segment
    and not one a token (8,192 states of 64 heads are 34 GB)."""
    import jax
    import jax.numpy as jnp

    b, s, heads, dk = q.shape
    seg = next(n for n in (64, 32, 16, 8, 4, 2, 1) if s % n == 0)
    segments = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        (s // seg, seg) + a.shape[:1] + a.shape[2:])

    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[..., None]
        err = vt - jnp.einsum("bhk,bhkv->bhv", kt, state)
        state = state + jnp.einsum("bhk,bhv->bhkv", kt * bt[..., None], err)
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state) / np.sqrt(dk)

    segment = jax.checkpoint(lambda state, xs: jax.lax.scan(step, state, xs))
    state = jnp.zeros((b, heads, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(segment, state, tuple(
        segments(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)


def kda_mix(x, w, cfg):
    """The linear-attention half on the normalised input ``x`` [b, s, h]."""
    import jax
    import jax.numpy as jnp

    b, s, _ = x.shape
    heads, d = cfg["linear_heads"], cfg["linear_head_dim"]
    split = lambda a: a.reshape(b, s, heads, d)
    branch = lambda n: split(jax.nn.silu(
        short_conv(x @ w["mix.w_" + n], w["mix.conv_" + n])))
    q, k, v = l2norm(branch("q")), l2norm(branch("k")), branch("v")
    g = -jnp.exp(w["mix.A_log"])[:, None] * split(jax.nn.softplus(
        (x @ w["mix.w_f1"]) @ w["mix.w_f2"] + w["mix.dt_bias"]))
    beta = 2.0 * jax.nn.sigmoid(x @ w["mix.w_b"])
    o = rms_norm(delta_rule(q, k, v, g, beta), w["mix.o_norm"], cfg["eps"])
    gate = jax.nn.sigmoid((x @ w["mix.w_g1"]) @ w["mix.w_g2"]
                          + w["mix.b_g"])
    return (o.reshape(b, s, heads * d) * gate) @ w["mix.w_o"]


@functools.lru_cache(maxsize=None)
def _attend_fn():
    import jax
    import jax.numpy as jnp

    def attend(q, k, v, row0):
        """Rows ``row0 : row0 + n`` of one key/value head's group: q [b, n,
        group, d] against k, v [b, s, d], causal."""
        s, d = k.shape[1], k.shape[2]
        scores = jnp.einsum("bqgd,bkd->bgqk", q, k) / np.sqrt(d)
        rows = row0 + jnp.arange(q.shape[1])[:, None]
        att = jax.nn.softmax(
            jnp.where(rows >= jnp.arange(s)[None, :], scores, -jnp.inf), -1)
        return jnp.einsum("bgqk,bkd->bqgd", att, v)

    return jax.jit(jax.checkpoint(attend))


def gqa_mix(x, w, cfg):
    """The softmax half: query head ``i`` on key/value head ``i // group``,
    causal, no rotation, no QK-norm, an elementwise gate. A masked softmax
    over all the keys, one key/value head's group at a time (K and V are
    used by each of the group's heads, which is what repeating them
    computes) and, where ``cfg["attention_rows"]`` says so and divides the
    length, that many query rows at a time, so that 8,192 tokens' scores
    fit; both as ``lax.map``, one block after the other."""
    import jax
    import jax.numpy as jnp

    b, s, _ = x.shape
    heads, kv, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    group, step = heads // kv, cfg.get("attention_rows") or s
    if s % step:
        step = s
    q = (x @ w["mix.w_q"]).reshape(b, s, kv, group, d)
    k = (x @ w["mix.w_k"]).reshape(b, s, kv, d)
    v = (x @ w["mix.w_v"]).reshape(b, s, kv, d)
    starts = jnp.arange(0, s, step)

    def one_group(a):
        qj, kj, vj = a                  # [b, s, group, d], [b, s, d] twice
        blocks = jnp.moveaxis(qj.reshape(b, s // step, step, group, d), 1, 0)
        o = jax.lax.map(lambda r: _attend_fn()(r[0], kj, vj, r[1]),
                        (blocks, starts))
        return jnp.moveaxis(o, 0, 1).reshape(b, s, group, d)

    o = jax.lax.map(one_group, tuple(jnp.moveaxis(a, 2, 0)
                                     for a in (q, k, v)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, s, heads * d)
    return (o * jax.nn.sigmoid(x @ w["mix.w_gate"])) @ w["mix.w_o"]


@functools.lru_cache(maxsize=None)
def _route_fn(top_k: int):
    import jax
    import jax.numpy as jnp

    def route(x, gate, bias):
        scores = jax.nn.sigmoid(x @ gate)                    # [T, E]
        picked, top_e = jax.lax.top_k(scores + bias, top_k + 1)
        top_s = jnp.take_along_axis(scores, top_e[:, :top_k], -1)
        weights = top_s / jnp.sum(top_s, -1, keepdims=True)
        near = picked[:, top_k - 1] - picked[:, top_k] \
            < NEAR_TIE * jnp.abs(picked[:, top_k - 1])
        # the last chosen and the first unchosen, where they nearly tie
        return weights, top_e[:, :top_k], jnp.where(
            near[:, None], top_e[:, top_k - 1:], -1)

    return jax.jit(route)


@functools.lru_cache(maxsize=None)
def _expert_fn():
    import jax

    def expert(x, w_gate, w_up, w_down, weight):
        """One SwiGLU expert on every token, times the token's routing
        weight for it (0 where it was not chosen)."""
        return weight[:, None] * (
            (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down)

    return jax.jit(jax.checkpoint(expert))


def moe(x, w, cfg, held=None, with_routing=False, shared=True):
    """The expert layer on ``x`` [T, h]: the held experts' part (all of
    them when ``held`` is None) plus, with ``shared``, the shared expert.
    ``w["mlp.w_*"]`` hold the held experts only. With ``with_routing``
    also ``rows`` [count], the assignments each held expert is given, and
    ``near`` [T], true for the tokens whose last chosen and first unchosen
    selection scores differ by under ``NEAR_TIE`` of their value and of
    which one is a held expert."""
    import jax.numpy as jnp

    weights, top_e, edge = _route_fn(cfg["top_k"])(
        x, w["mlp.gate"], w["mlp.select_bias"])
    first, count = held or (0, w["mlp.gate"].shape[1])
    near = jnp.any((edge >= first) & (edge < first + count), -1)
    y = jnp.zeros_like(x)
    rows = []
    for e in range(count):
        chosen = top_e == first + e
        rows.append(jnp.sum(chosen))
        y = y + _expert_fn()(
            x, w["mlp.w_gate"][e], w["mlp.w_up"][e], w["mlp.w_down"][e],
            jnp.sum(jnp.where(chosen, weights, 0.0), -1))
    if shared:
        y = y + _expert_fn()(
            x, w["mlp.shared_gate"], w["mlp.shared_up"],
            w["mlp.shared_down"], jnp.ones((x.shape[0],), jnp.float32))
    if not with_routing:
        return y
    return y, jnp.stack(rows), near


def is_kda(w: dict) -> bool:
    return "mix.A_log" in w


def layer(x, w, cfg, held=None):
    """One layer on ``x`` [b, s, h]: ``(x, routing)``; ``routing`` holds
    the mix's input and output, the expert layer's input ``x`` and output
    ``y`` ([b s, h]), ``rows``, ``near`` [b s] (``moe``'s) and
    ``near_ties``, their count."""
    b, s, h = x.shape
    y_in = rms_norm(x, w["ln_1.weight"], cfg["eps"])
    mixed = (kda_mix if is_kda(w) else gqa_mix)(y_in, w, cfg)
    x = x + mixed
    m_in = rms_norm(x, w["ln_2.weight"], cfg["eps"]).reshape(b * s, h)
    y, rows, near = moe(m_in, w, cfg, held, with_routing=True)
    return x + y.reshape(b, s, h), {
        "mix_in": y_in, "mix_out": mixed, "x": m_in, "y": y, "rows": rows,
        "near": near, "near_ties": near.sum()}


def forward(layer_weights, other: dict, tokens, cfg: dict, held=None,
            routing=None):
    """Logits ``[b, s, vocab]`` of ``tokens`` [b, s], float32.
    ``layer_weights`` yields one dict a layer (keys as the program names
    them inside a layer), ``other`` holds ``wte.weight``, ``ln_f.weight``
    and ``lm_head.weight`` [h, vocab]; ``cfg``: ``heads``, ``kv_heads``,
    ``head_dim``, ``linear_heads``, ``linear_head_dim``, ``top_k``,
    ``eps`` and, optionally, ``attention_rows``. A list given as
    ``routing`` is filled with a dict a layer."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = _f32(other["wte.weight"])[np.asarray(tokens)]
        for w in layer_weights:
            x, info = layer(x, {k: _f32(v) for k, v in w.items()}, cfg,
                            held)
            if routing is not None:
                routing.append(info)
        out = rms_norm(x, _f32(other["ln_f.weight"]), cfg["eps"]) \
            @ _f32(other["lm_head.weight"])
    return jnp.asarray(out, jnp.float32)


def next_token_loss(lg, tokens):
    """Mean cross entropy of each position's logits against the next
    token of its sequence."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(lg[:, :-1], -1)
    nxt = jnp.asarray(np.asarray(tokens)[:, 1:])
    return -jnp.take_along_axis(logp, nxt[..., None], -1).mean()


def loss(layer_weights, other, tokens, cfg, held=None):
    """The training loss: next-token cross entropy over the vocabulary
    held, nothing added. Differentiable towards the weights
    (``jax.grad``)."""
    return next_token_loss(
        forward(layer_weights, other, tokens, cfg, held), tokens)
