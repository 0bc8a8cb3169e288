"""Solar-Open2 (huggingface.co/upstage/Solar-Open2-250B config.json): a
hybrid of softmax and linear attention over a fine-grained mixture of
experts, under the pipeline protocol of ``distributed/hybrid.py``.

Layers come in **periods** of four: one grouped-query softmax layer without
any position embedding and with an elementwise sigmoid gate on its output,
then three gated delta-rule linear-attention layers with a decay a key
channel (KDA, arXiv:2510.26692; ``ops/kda.py``). Every layer's FFN is a
token-choice mixture (sigmoid scores, a selection bias, the chosen scores
renormalised) plus one shared expert (``distributed/moe.held_moe``).
``experts_held`` makes the expert layers one rank's share of an
expert-parallel layout: the router keeps its ``n_routed_experts`` outputs
and this model holds ``experts_held[1]`` of the experts.

**The stacked unit is the period.** ``HybridPipelineTrainer`` stacks one
kind of block and scans over the stack, so ``pipeline_blocks()`` are
``SolarOpen2Period``s, four unlike layers each. **Recomputation is a
layer's**: every layer's forward is a pure function of its input and its
weights under ``jax.checkpoint``, so what is kept between forward and
backward is one activation a layer and the forward runs twice a step. The
configuration therefore trains with ``strategy.recompute`` off: the
trainer's own checkpoint would go round the whole period and recompute a
third time (with it off, a period's saved activations are its four
inputs, not the 6-8 GB inside its layers).

``models/solar_open2_reference.py`` is the plain float32 reference of the
same equations, token by token; it reads this model's weights by the names
given here and none of its code.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed import context as _dctx
from ..distributed.moe import HeldMoEMLP, held_moe, publish_expert_load
from ..distributed.parallel_layers import (ColumnParallelLinear,
                                           VocabParallelEmbedding)
from ..framework.tensor import Tensor
from ..nn import initializer as I
from ..ops import flash_attention as _fa
from ..ops.kda import kda_attention_flat
from ..ops.kda_prep import causal_conv  # noqa: F401  (tests read it here)
from ..ops.kda_prep import (head_sums as _head_sums, kda_prep,
                            over_heads as _over_heads)
from ..profiler.trace import annotate
from ..tensor._helper import apply

_F32 = jnp.float32
#: what l2norm adds under the root (fla.modules.l2norm)
L2_EPS = 1e-6


@dataclass
class SolarOpen2Config:
    """Sizes under the names of the model's ``config.json``."""
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    linear_attn_num_heads: int = 64        # linear_attn_config.num_heads
    linear_attn_head_dim: int = 128        # linear_attn_config.head_dim
    short_conv_kernel_size: int = 4
    kda_proj_rank: int = 128               # kda_use_full_proj false: W_f1, W_g1
    gqa_interval: int = 3                  # a softmax layer, then 3 linear
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    #: the embedding's rows' deviation and the selection bias's (the bias
    #: starts at 0 in the lineage and a balancing rule moves it, which is
    #: no part of a step). A run from seeded weights that wants its routing
    #: spread over the experts from step 0 sets both, as
    #: perfbench/configs/solar-open2-250b-train.json does and says why
    embedding_range: float = 0.02
    select_bias_range: float = 0.0
    #: (first, count): the routed experts held here; None: all of them
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.num_hidden_layers % self.period:
            raise ValueError(
                f"num_hidden_layers {self.num_hidden_layers} is not whole "
                f"periods of {self.period}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is what is implemented")

    @property
    def period(self) -> int:
        return self.gqa_interval + 1

    @property
    def moe_num_experts(self) -> int:
        """What the trainer asks to know that blocks carry ``aux_loss``
        and ``aux_stats``."""
        return self.n_routed_experts

    @property
    def held(self) -> int:
        return self.experts_held[1] if self.experts_held \
            else self.n_routed_experts

    @staticmethod
    def solar_open2_250b():
        """The catalog row: 48 layers, 320 experts, 196,608 words."""
        return SolarOpen2Config()

    @staticmethod
    def tiny(**kw):
        """Unit-test sizes: one period, heads of 128 so that the Pallas
        scan takes them, 16 experts of which 4 a token."""
        base = dict(
            vocab_size=512, hidden_size=128, num_hidden_layers=4,
            num_attention_heads=2, num_key_value_heads=1,
            linear_attn_num_heads=2, kda_proj_rank=16,
            moe_intermediate_size=64, n_routed_experts=16,
            num_experts_per_tok=4, max_position_embeddings=1024)
        base.update(kw)
        return SolarOpen2Config(**base)

    # -- counts ----------------------------------------------------------
    def layer_params(self) -> dict:
        """Parameters of one layer by part: ``kda`` and ``gqa`` (the
        attention halves), ``dense`` (router, norms, shared expert) and
        ``expert`` (one routed expert)."""
        h, r = self.hidden_size, self.kda_proj_rank
        qd = self.num_attention_heads * self.head_dim
        kvd = self.num_key_value_heads * self.head_dim
        ld = self.linear_attn_num_heads * self.linear_attn_head_dim
        f = self.moe_intermediate_size
        return {
            "kda": 3 * h * ld + 3 * self.short_conv_kernel_size * ld
            + 2 * (h * r + r * ld) + 2 * ld + self.linear_attn_num_heads
            + h * self.linear_attn_num_heads + self.linear_attn_head_dim
            + ld * h,
            "gqa": 3 * h * qd + 2 * h * kvd,
            "dense": h * self.n_routed_experts + self.n_routed_experts
            + 2 * h + 3 * h * f * self.n_shared_experts,
            "expert": 3 * h * f}

    def num_params(self) -> int:
        """Parameters held: every layer's attention half, router, norms
        and shared expert, the held experts, embedding, final norm and
        head."""
        p = self.layer_params()
        n_gqa = self.num_hidden_layers // self.period
        return (n_gqa * p["gqa"]
                + (self.num_hidden_layers - n_gqa) * p["kda"]
                + self.num_hidden_layers * (p["dense"]
                                            + self.held * p["expert"])
                + 2 * self.vocab_size * self.hidden_size + self.hidden_size)

    def active_params(self) -> int:
        """Parameters a token multiplies with where every expert is held:
        ``num_experts_per_tok`` routed experts a layer, not all; the
        embedding's lookup is no product."""
        p = self.layer_params()
        n_gqa = self.num_hidden_layers // self.period
        return (n_gqa * p["gqa"]
                + (self.num_hidden_layers - n_gqa) * p["kda"]
                + self.num_hidden_layers * (
                    p["dense"] + self.num_experts_per_tok * p["expert"])
                + self.vocab_size * self.hidden_size)

    def flops_per_token(self, seq_len=None) -> float:
        """Training operations a token, all experts held: 6 a parameter it
        multiplies with, Megatron's 12 s h' for each softmax layer (h' the
        heads' total width) and the linear layers' state updates
        (ops/kda.py: 3 products of dk x dv a head, forward, twice that
        backward)."""
        s = seq_len or self.max_position_embeddings
        n_gqa = self.num_hidden_layers // self.period
        scan = 3 * 2 * 3 * self.linear_attn_num_heads \
            * self.linear_attn_head_dim ** 2
        return (6.0 * self.active_params()
                + 12.0 * n_gqa * self.num_attention_heads * self.head_dim
                * s + (self.num_hidden_layers - n_gqa) * scan)


# ---------------------------------------------------------------------------
# the layers as pure functions of (x, weights): what jax.checkpoint wraps
# ---------------------------------------------------------------------------
def rms_norm(x, w, eps):
    xf = x.astype(_F32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * w.astype(_F32)).astype(x.dtype)


def kda_mix(x, w, c: SolarOpen2Config):
    """The linear-attention half of a layer on the normalised input. What
    turns the three projections into the scan's operands (short
    convolution, SiLU, q's and k's ``l2norm`` a head) is ``ops/kda_prep.py``:
    one Pallas pass each way over the raw bf16 projections on the chip, its
    ``jax.numpy`` spelling elsewhere; the products stay three, and ``g``,
    ``beta`` and ``gate`` stay here, fused by XLA into their products."""
    heads, d = c.linear_attn_num_heads, c.linear_attn_head_dim
    dt = x.dtype
    with annotate("blk/kda/proj"):
        q, k, v = kda_prep(
            [jnp.dot(x, w["w_" + n]) for n in "qkv"],
            [w["conv_" + n] for n in "qkv"], (True, True, False), d, L2_EPS)
        f = jnp.dot(jnp.dot(x, w["w_f1"]), w["w_f2"]).astype(_F32) \
            + w["dt_bias"].astype(_F32)
        g = -jnp.repeat(jnp.exp(w["A_log"].astype(_F32)), d) \
            * jax.nn.softplus(f)
        beta = 2.0 * jax.nn.sigmoid(jnp.dot(x, w["w_b"]).astype(_F32))
        gate = jax.nn.sigmoid(
            jnp.dot(jnp.dot(x, w["w_g1"]), w["w_g2"]).astype(_F32)
            + w["b_g"].astype(_F32)).astype(dt)
    with annotate("blk/kda/scan"):
        o = kda_attention_flat(q, k, v, g, beta)
    with annotate("blk/kda/out"):
        # RMSNorm over a head's columns, its weight of d repeated a head
        of = o.astype(_F32)
        inv = jax.lax.rsqrt(_head_sums(of * of, heads) / d
                            + c.rms_norm_eps)
        o = of * _over_heads(inv, d) \
            * jnp.tile(w["o_norm"].astype(_F32), heads)
        return jnp.dot((o * gate.astype(_F32)).astype(dt), w["w_o"])


def gqa_mix(x, w, c: SolarOpen2Config):
    """The softmax half: grouped-query attention, no rotation, no QK-norm,
    an elementwise sigmoid gate on the heads' output."""
    b, s, _ = x.shape
    heads, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with annotate("blk/qkv"):
        q = jnp.dot(x, w["w_q"]).reshape(b, s, heads, d)
        k = jnp.dot(x, w["w_k"]).reshape(b, s, kv, d)
        v = jnp.dot(x, w["w_v"]).reshape(b, s, kv, d)
    with annotate("blk/attn"):
        if _fa.supported(q.shape, None, 0.0, kv_seq=s, kv_heads=kv):
            o = _fa.flash_mha(q, k, v, causal=True)
        else:
            o = _fa.mha_reference(q, k, v, causal=True)
    with annotate("blk/attn_out"):
        gate = jax.nn.sigmoid(jnp.dot(x, w["w_gate"]).astype(_F32))
        o = (o.reshape(b, s, heads * d).astype(_F32) * gate).astype(x.dtype)
        return jnp.dot(o, w["w_o"])


def layer_forward(x, w, c: SolarOpen2Config, kind: str, moe_options):
    """One pre-norm layer: ``x + Mix(norm x)``, then ``x + MoE(norm x)``.
    ``w``: the layer's values by name. Returns ``(x, rows [held],
    load_max, assigned, routed)``, the expert layer's counts, float32."""
    b, s, h = x.shape
    mix = {k[4:]: v for k, v in w.items() if k.startswith("mix.")}
    mlp = {k[4:]: v for k, v in w.items() if k.startswith("mlp.")}
    if kind == "kda":
        with annotate("blk/kda/proj"):
            y = rms_norm(x, w["ln_1.weight"], c.rms_norm_eps)
        y = kda_mix(y, mix, c)
        with annotate("blk/kda/out"):
            x = x + y
    else:
        with annotate("blk/qkv"):
            y = rms_norm(x, w["ln_1.weight"], c.rms_norm_eps)
        y = gqa_mix(y, mix, c)
        with annotate("blk/attn_out"):
            x = x + y
    with annotate("blk/ffn"):
        y = rms_norm(x, w["ln_2.weight"], c.rms_norm_eps)
        y, rows = held_moe(
            y.reshape(b * s, h), mlp["gate"], mlp["w_gate"], mlp["w_up"],
            mlp["w_down"], c.num_experts_per_tok, **moe_options(mlp))
        x = x + y.reshape(b, s, h)
    rows = rows.astype(_F32)
    return (x, rows, jnp.max(rows), jnp.sum(rows),
            jnp.float32(b * s * c.num_experts_per_tok))


# ---------------------------------------------------------------------------
# the layers as modules: they hold the weights and name them
# ---------------------------------------------------------------------------
class SolarKDA(nn.Layer):
    """Weights of a linear-attention half. ``A_log`` and ``dt_bias`` are
    drawn so that the decays ``exp(g)`` spread over about 0.5-0.999: ``A``
    log-uniform in [1, 8] a head, ``softplus(dt_bias)`` log-uniform in
    [0.001, 0.09] a channel (fla's ranges, narrowed at the top)."""

    def __init__(self, c: SolarOpen2Config):
        super().__init__()
        h, r = c.hidden_size, c.kda_proj_rank
        heads, d = c.linear_attn_num_heads, c.linear_attn_head_dim
        ld, taps = heads * d, c.short_conv_kernel_size
        init = I.Normal(0.0, c.initializer_range)
        out_init = I.Normal(0.0, c.initializer_range
                            / math.sqrt(2 * c.num_hidden_layers))
        conv = I.Uniform(-taps ** -0.5, taps ** -0.5)

        def new(name, shape, initializer=init):
            setattr(self, name, self.create_parameter(
                shape, default_initializer=initializer))

        for n in "qkv":
            new("w_" + n, [h, ld])
        for n in "qkv":
            new("conv_" + n, [taps, ld], conv)
        new("w_f1", [h, r])
        new("w_f2", [r, ld])
        new("dt_bias", [ld], I.Uniform(math.log(1e-3), math.log(0.09)))
        new("A_log", [heads], I.Uniform(0.0, math.log(8.0)))
        new("w_b", [h, heads])
        new("w_g1", [h, r])
        new("w_g2", [r, ld])
        new("b_g", [ld], I.Constant(0.0))
        new("o_norm", [d], I.Constant(1.0))
        new("w_o", [ld, h], out_init)


class SolarGQA(nn.Layer):
    def __init__(self, c: SolarOpen2Config):
        super().__init__()
        h = c.hidden_size
        qd = c.num_attention_heads * c.head_dim
        kvd = c.num_key_value_heads * c.head_dim
        init = I.Normal(0.0, c.initializer_range)
        self.w_q = self.create_parameter([h, qd], default_initializer=init)
        self.w_k = self.create_parameter([h, kvd], default_initializer=init)
        self.w_v = self.create_parameter([h, kvd], default_initializer=init)
        self.w_gate = self.create_parameter([h, qd],
                                            default_initializer=init)
        self.w_o = self.create_parameter(
            [qd, h], default_initializer=I.Normal(
                0.0, c.initializer_range
                / math.sqrt(2 * c.num_hidden_layers)))


class SolarOpen2Layer(nn.Layer):
    """One layer, ``kind`` "gqa" or "kda". ``forward`` is
    ``layer_forward`` under ``jax.checkpoint``; the expert layer's counts
    of the last forward are in ``stats``."""

    def __init__(self, c: SolarOpen2Config, kind: str):
        super().__init__()
        self.config, self.kind = c, kind
        self.ln_1 = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.mix = SolarKDA(c) if kind == "kda" else SolarGQA(c)
        self.ln_2 = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.mlp = HeldMoEMLP(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            top_k=c.num_experts_per_tok,
            held=c.experts_held or (0, c.n_routed_experts),
            initializer_range=c.initializer_range,
            out_initializer_range=c.initializer_range
            / math.sqrt(2 * c.num_hidden_layers),
            select_bias_range=c.select_bias_range,
            shared_width=c.moe_intermediate_size)
        self.stats = {}

    def forward(self, x):
        names, tensors = zip(*self.named_parameters())
        c, kind, options = self.config, self.kind, self.mlp.options

        @jax.checkpoint
        def f(xv, *values):
            return layer_forward(xv, dict(zip(names, values)), c, kind,
                                 options)

        out, rows, load_max, assigned, routed = apply(
            f, x, *tensors, name="solar_open2_" + kind)
        self.stats = {"moe/rows": rows, "moe/load_max": load_max,
                      "moe/assigned": assigned, "moe/routed": routed}
        return out


class SolarOpen2Period(nn.Layer):
    """The stacked unit: a softmax layer and ``gqa_interval`` linear ones.
    ``aux_loss`` (zero: the model adds no auxiliary term) and
    ``aux_stats`` (the four expert layers' counts, summed) are what the
    trainer's auxiliary carry takes from a block."""

    def __init__(self, c: SolarOpen2Config):
        super().__init__()
        self.layers = nn.LayerList(
            [SolarOpen2Layer(c, "gqa")]
            + [SolarOpen2Layer(c, "kda") for _ in range(c.gqa_interval)])
        self.aux_loss = Tensor(jnp.zeros((), _F32))
        self.aux_stats = {}

    def forward(self, x):
        stats = None
        for layer in self.layers:
            x = layer(x)
            stats = layer.stats if stats is None else {
                k: stats[k] + v for k, v in layer.stats.items()}
        self.aux_loss = Tensor(jnp.zeros((), _F32))
        self.aux_stats = stats
        return x


class SolarOpen2(nn.Layer):
    """Decoder-only Solar-Open2. ``forward`` returns logits, ``loss`` the
    next-token cross entropy over the vocabulary held."""

    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        c = self.config = config
        self.wte = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size,
            weight_attr=I.Normal(0.0, c.embedding_range))
        self.periods = nn.LayerList(
            [SolarOpen2Period(c)
             for _ in range(c.num_hidden_layers // c.period)])
        self.ln_f = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.lm_head = ColumnParallelLinear(
            c.hidden_size, c.vocab_size, has_bias=False,
            weight_attr=I.Normal(0.0, c.initializer_range),
            gather_output=True)

    def forward(self, tokens):
        x = self.wte(tokens)
        for period in self.periods:
            x = period(x)
        return self.lm_head(self.ln_f(x))

    # --- pipeline protocol (distributed/hybrid.py) -----------------------
    def pipeline_stem(self, tokens):
        return self.wte(tokens)

    def pipeline_blocks(self):
        return self.periods

    def pipeline_head(self, x, tokens, labels=None):
        """Final norm and the fused head and cross entropy, as GPT's."""
        from ..ops.fused_ce import fused_linear_cross_entropy

        chunk = None if _dctx.current_sequence_parallel() else 256
        lbl, next_token = (tokens, True) if labels is None \
            else (labels, False)
        return fused_linear_cross_entropy(
            self.ln_f(x), self.lm_head.weight, lbl, chunk=chunk,
            transpose_w=True, next_token=next_token)

    def loss(self, tokens, labels=None):
        x = self.wte(tokens)
        for period in self.periods:
            x = period(x)
        return self.pipeline_head(x, tokens, labels=labels)

    def publish_aux_stats(self, stats):
        """A training step's ``aux_stats`` (host values) into the
        profiler's registry: ``moe/dropped_tokens`` and
        ``moe/expert_load_max_over_mean`` over the held experts."""
        publish_expert_load(stats)
