"""GPT model family — the flagship for the hybrid-parallel north star
(BASELINE.md: GPT-3 1.3B/13B, TP×PP×sharding, ≥45% MFU target).

The reference has no GPT in-tree (its GPT configs ran via fleet meta
optimizers over user model code); here the model is first-class and
TPU-first:
  - attention through F.scaled_dot_product_attention (flash path),
  - q/kv/mlp projections as tensor-parallel layers carrying PartitionSpecs
    (distributed/parallel_layers.py) that the strategy compiler turns into
    GSPMD shardings,
  - identical block structure per layer so the compiled path can stack
    block params into [L, ...] arrays and lax.scan over layers (and shard
    the stage axis for pipeline parallelism).
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..distributed import context as _dctx
from ..distributed.parallel_layers import (ColumnParallelLinear,
                                           RowParallelLinear,
                                           VocabParallelEmbedding)
from ..nn import functional as F
from ..nn import initializer as I
from ..profiler import trace as _ptrace
from ..profiler.trace import annotate
from ..tensor import arange
from .tick import LoopRecord, rms


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    ffn_hidden_size: int = 0          # default 4*hidden
    dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    use_flash_attention: bool = True
    # MoE (beyond-reference capability, distributed/moe.py): >0 replaces
    # every block's FFN with a num-experts MoE sharded over 'ep'
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # The architecture of the block. These describe a model, none is a
    # policy; at the values below the traced program is the GPT-3 one.
    norm: str = "layernorm"           # | "rmsnorm" (no bias, HF OlmoeRMSNorm)
    position: str = "learned"         # | "rope": no wpe, q and k rotated
    rope_theta: float = 10000.0
    qk_norm: bool = False             # RMSNorm of q and of k, all heads wide
    bias: bool = True                 # biases on the block's projections
    ffn: str = "gelu"                 # | "swiglu": down(silu(gate x) * up x)
    moe_expert_width: int = 0         # one expert's width; 0: ffn_hidden_size
    moe_dropless: bool = False        # token choice, no capacity, no drops
    moe_z_weight: float = 0.0         # router z-loss (drop-less path)
    sandwich_norm: bool = False       # a norm after each sub-layer as well
    # a looped stack (LoopLM, arXiv:2510.25741): the same num_layers run
    # loop_steps times, the final norm and an exit gate after each, and a
    # cache for every (step, layer). exit_threshold: the cumulative exit
    # probability at which a position's head stops reading later steps
    loop_steps: int = 1
    exit_threshold: float = 1.0

    def __post_init__(self):
        if not self.ffn_hidden_size:
            self.ffn_hidden_size = 4 * self.hidden_size
        if self.norm not in ("layernorm", "rmsnorm") or \
                self.position not in ("learned", "rope") or \
                self.ffn not in ("gelu", "swiglu"):
            raise ValueError(f"unknown block kind: norm {self.norm!r}, "
                             f"position {self.position!r}, ffn {self.ffn!r}")
        if self.moe_dropless and (self.ffn != "swiglu" or self.bias):
            raise ValueError("moe_dropless experts are bias-free SwiGLU "
                             "(distributed/moe.py dropless_moe)")
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps {self.loop_steps} must be >= 1")

    def is_gpt3_block(self) -> bool:
        """Whether every architecture field has GPT-3's value: the block
        whose served program is pinned, and the only one the speculative
        draft tick (serving/spec.py) runs."""
        return (self.norm == "layernorm" and self.position == "learned"
                and not self.qk_norm and self.bias and self.ffn == "gelu"
                and not self.moe_dropless and not self.sandwich_norm
                and self.loop_steps == 1)

    # presets from the reference north-star table (BASELINE.md)
    @staticmethod
    def gpt3_125m():
        return GPTConfig(hidden_size=768, num_layers=12, num_heads=12)

    @staticmethod
    def gpt3_350m():
        return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16)

    @staticmethod
    def gpt3_1_3b():
        return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                         max_seq_len=2048)

    @staticmethod
    def gpt3_2_7b():
        return GPTConfig(hidden_size=2560, num_layers=32, num_heads=32,
                         max_seq_len=2048)

    @staticmethod
    def gpt3_6_7b():
        return GPTConfig(hidden_size=4096, num_layers=32, num_heads=32,
                         max_seq_len=2048)

    @staticmethod
    def gpt3_13b():
        return GPTConfig(hidden_size=5120, num_layers=40, num_heads=40,
                         max_seq_len=2048)

    @staticmethod
    def olmoe_1b_7b():
        """OLMoE-1B-7B (huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct
        config.json; loss weights and "drop-less" from arXiv:2409.02060):
        64 experts of width 1024, 8 a token, unrenormalised."""
        return GPTConfig(
            vocab_size=50304, hidden_size=2048, num_layers=16, num_heads=16,
            max_seq_len=4096, ffn_hidden_size=8192, layer_norm_eps=1e-5,
            tie_word_embeddings=False, norm="rmsnorm", position="rope",
            rope_theta=10000.0, qk_norm=True, bias=False, ffn="swiglu",
            moe_num_experts=64, moe_top_k=8, moe_expert_width=1024,
            moe_dropless=True, moe_aux_weight=0.01, moe_z_weight=0.001)

    @staticmethod
    def ouro_2_6b():
        """Ouro-2.6B (huggingface.co/ByteDance/Ouro-2.6B config.json; the
        loop, the sandwich norms and the exit gate from arXiv:2510.25741
        and the published modeling code): 48 shared layers run 4 times."""
        return GPTConfig(
            vocab_size=49152, hidden_size=2048, num_layers=48, num_heads=16,
            max_seq_len=65536, ffn_hidden_size=5632, layer_norm_eps=1e-6,
            tie_word_embeddings=False, norm="rmsnorm", position="rope",
            rope_theta=1000000.0, bias=False, ffn="swiglu",
            sandwich_norm=True, loop_steps=4, exit_threshold=1.0)

    def num_params(self) -> int:
        h, L, v = self.hidden_size, self.num_layers, self.vocab_size
        f = self.moe_expert_width or self.ffn_hidden_size
        mats = 3 if self.ffn == "swiglu" else 2   # matrices of one FFN
        e = max(self.moe_num_experts, 1)          # E expert FFNs + router
        norm = h * (1 if self.norm == "rmsnorm" else 2)
        biases = (4 * h + e * (f * (mats - 1) + h)) if self.bias else 0
        per_block = 4 * h * h + e * mats * h * f + biases \
            + (2 + 2 * self.qk_norm + 2 * self.sandwich_norm) * norm \
            + (h * e if self.moe_num_experts else 0)
        return v * h * (1 if self.tie_word_embeddings else 2) \
            + (self.max_seq_len * h if self.position == "learned" else 0) \
            + L * per_block + norm \
            + (h + 1 if self.loop_steps > 1 else 0)     # the exit gate

    def flops_per_token(self, seq_len=None) -> float:
        """Training FLOPs/token ≈ 6N + 12·L·h·s (attention term)."""
        s = seq_len or self.max_seq_len
        return 6.0 * self.num_params() + 12.0 * self.num_layers * \
            self.hidden_size * s


def rope_rotate(x, theta: float):
    """Rotary position embedding of ``x`` [b, s, heads, d] at positions
    0..s-1, in the HF rotate-half convention: the angle of pair
    ``(i, i + d/2)`` at position ``p`` is ``p / theta**(2i/d)``. Computed in
    float32 and cast back."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return (xf * cos + half * sin).astype(x.dtype)


def rope_at(x, pos, theta: float):
    """``rope_rotate``'s rotation at given positions: ``x`` [..., t, heads,
    d], ``pos`` (int, may be traced) broadcastable to ``x.shape[:-2]``.
    The serving forwards rotate q and k by each token's own cache position,
    so the cache holds rotated keys."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.broadcast_to(pos, x.shape[:-2]).astype(
        jnp.float32)[..., None, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return (xf * cos + half * sin).astype(x.dtype)


def loop_exit(states, gates, threshold: float):
    """The looped stack's exit rule, per position. ``states`` [T, ..., h]
    are the final norm's outputs after each loop step, ``gates`` [T, ...]
    (float32) the exit gate's sigmoid there. ``p_t = g_t prod_{j<t}(1 -
    g_j)`` for ``t < T`` and ``p_T`` the remainder; a position exits at the
    first step whose cumulative ``p`` reaches ``threshold``, else at ``T``.
    Returns (the chosen step's state [..., h], the expected exit step
    ``sum_t t p_t`` and the chosen step, both float32 [...], steps counted
    from 1). Every step has run by then: later tokens attend to every
    step's keys, so the rule picks what the head reads, not what runs."""
    steps = states.shape[0]
    stay = jnp.cumprod(1.0 - gates[:-1], axis=0)            # [T-1, ...]
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stay[:-1]], 0)
    p = jnp.concatenate([gates[:-1] * before, stay[-1:]], 0)   # [T, ...]
    t = jnp.arange(1, steps + 1, dtype=jnp.float32).reshape(
        (steps,) + (1,) * (gates.ndim - 1))
    expected = jnp.sum(t * p, axis=0)
    reached = jnp.cumsum(p[:-1], axis=0) >= threshold
    chosen = jnp.where(reached.any(0), jnp.argmax(reached, axis=0),
                       steps - 1)
    out = states[steps - 1]
    for i in range(steps - 2, -1, -1):      # a where over the T states
        out = jnp.where((chosen == i)[..., None], states[i], out)
    return out, expected, (chosen + 1).astype(jnp.float32)


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        init = I.Normal(0.0, c.initializer_range)
        out_init = I.Normal(0.0, c.initializer_range /
                            math.sqrt(2 * c.num_layers))
        self.num_heads = c.num_heads
        self.head_dim = c.hidden_size // c.num_heads
        self.qkv_proj = ColumnParallelLinear(
            c.hidden_size, 3 * c.hidden_size, weight_attr=init,
            has_bias=c.bias, gather_output=False)
        self.out_proj = RowParallelLinear(
            c.hidden_size, c.hidden_size, weight_attr=out_init,
            has_bias=c.bias)
        self.dropout = c.dropout
        self.rope_theta = c.rope_theta if c.position == "rope" else None
        if c.qk_norm:
            # over the whole projection, before the heads are split (HF
            # OlmoeAttention.q_norm/k_norm)
            self.q_norm = nn.RMSNorm(c.hidden_size, epsilon=c.layer_norm_eps)
            self.k_norm = nn.RMSNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        else:
            self.q_norm = self.k_norm = None
        # qkv weight columns interleave q|k|v: shard on out dim stays valid
        self.qkv_proj.param_shardings = {"weight": P(None, "tp"),
                                         "bias": P("tp")}

    def forward(self, x):
        # blk/* scope names: one vocabulary with gpt_block_body (the
        # serving forwards), read by the traced run's per-part metrics
        b, s, h = x.shape[0], x.shape[1], x.shape[2]
        with annotate("blk/qkv"):
            qkv = self.qkv_proj(x)
            qkv = qkv.reshape([b, s, 3, self.num_heads, self.head_dim])
            q, k, v = qkv.unbind(2)
            if self.q_norm is not None:
                heads = [b, s, self.num_heads, self.head_dim]
                q = self.q_norm(q.reshape([b, s, h])).reshape(heads)
                k = self.k_norm(k.reshape([b, s, h])).reshape(heads)
            if self.rope_theta is not None:
                q, k = self._rotate(q, k)
        with annotate("blk/attn"):
            out = self._attend(q, k, v)
        with annotate("blk/attn_out"):
            return self.out_proj(out.reshape([b, s, h]))

    def _rotate(self, q, k):
        sp = _dctx.current_sequence_parallel()
        if sp is not None and sp[2]:
            raise NotImplementedError(
                "RoPE inside a manual sequence-parallel region needs the "
                "shard's position offset; use sp_degree=1 or pp=1")
        from ..tensor._helper import apply

        theta = self.rope_theta
        return apply(lambda q_, k_: (rope_rotate(q_, theta),
                                     rope_rotate(k_, theta)),
                     q, k, name="rope")

    def _attend(self, q, k, v):
        sp = _dctx.current_sequence_parallel()
        dropout_active = bool(self.dropout) and self.training
        if sp is not None:
            # sequence-parallel: ring attention over the 'sp' mesh axis
            # (ops/ring_attention.py) — seq dim stays sharded end to end.
            # Attention-prob dropout is not expressible in the ring (probs
            # never materialize): under sp it must be off. Inside the
            # manual region there is NO correct fallback (plain attention
            # would be block-diagonal over the local shard), so raise.
            from ..ops.ring_attention import (_ring_mha,
                                              sequence_parallel_attention)
            from ..tensor._helper import apply

            mesh, axis, manual = sp
            if dropout_active:
                raise NotImplementedError(
                    "attention-probability dropout is not supported under "
                    "sequence parallelism (ring attention); set "
                    "GPTConfig.dropout=0 or sp_degree=1")
            if manual:
                # already inside a shard_map manual over `axis`; capture
                # the remaining-auto-axes scope NOW — the custom_vjp
                # backward traces at transpose time, after the scope exits
                auto_ctx = _dctx.current_auto_axes()
                fn = lambda q_, k_, v_: _ring_mha(q_, k_, v_, True, None,
                                                  axis, auto_ctx)
            else:
                fn = lambda q_, k_, v_: sequence_parallel_attention(
                    q_, k_, v_, mesh, causal=True, axis_name=axis)
            return apply(fn, q, k, v, name="ring_attention")
        return F.scaled_dot_product_attention(
            q, k, v, is_causal=True, dropout_p=self.dropout,
            training=self.training)


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        init = I.Normal(0.0, c.initializer_range)
        out_init = I.Normal(0.0, c.initializer_range /
                            math.sqrt(2 * c.num_layers))
        self.fc_in = ColumnParallelLinear(c.hidden_size, c.ffn_hidden_size,
                                          weight_attr=init, has_bias=c.bias,
                                          gather_output=False)
        # SwiGLU: fc_in is the "up" projection, fc_gate goes through silu
        self.fc_gate = ColumnParallelLinear(
            c.hidden_size, c.ffn_hidden_size, weight_attr=init,
            has_bias=c.bias, gather_output=False) \
            if c.ffn == "swiglu" else None
        self.fc_out = RowParallelLinear(c.ffn_hidden_size, c.hidden_size,
                                        weight_attr=out_init,
                                        has_bias=c.bias)
        self.dropout = c.dropout

    def forward(self, x):
        if self.fc_gate is not None:
            x = F.silu(self.fc_gate(x)) * self.fc_in(x)
        else:
            x = F.gelu(self.fc_in(x), approximate=True)
        x = self.fc_out(x)
        return F.dropout(x, self.dropout, training=self.training)


def _norm(config: GPTConfig):
    if config.norm == "rmsnorm":
        return nn.RMSNorm(config.hidden_size, epsilon=config.layer_norm_eps)
    return nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)


class GPTBlock(nn.Layer):
    """Pre-norm transformer block; identical structure per layer (stackable)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config            # read at forward time
        self.ln_1 = _norm(config)
        self.attn = GPTAttention(config)
        self.ln_2 = _norm(config)
        if config.moe_dropless:
            from ..distributed.moe import DroplessMoEMLP

            self.mlp = DroplessMoEMLP(
                config.hidden_size,
                config.moe_expert_width or config.ffn_hidden_size,
                config.moe_num_experts, top_k=config.moe_top_k,
                initializer_range=config.initializer_range,
                out_initializer_range=config.initializer_range /
                math.sqrt(2 * config.num_layers))
        elif config.moe_num_experts > 0:
            from ..distributed.moe import MoEMLP

            self.mlp = MoEMLP(config.hidden_size, config.ffn_hidden_size,
                              config.moe_num_experts,
                              top_k=config.moe_top_k,
                              capacity_factor=config.moe_capacity_factor,
                              initializer_range=config.initializer_range)
        else:
            self.mlp = GPTMLP(config)
        # sandwich norms: each sub-layer's output is normed before it
        # joins the residual stream
        self.post_attn_norm = _norm(config) if config.sandwich_norm else None
        self.post_ffn_norm = _norm(config) if config.sandwich_norm else None
        #: the block's auxiliary loss of its last forward, already
        #: weighted: what GPT.loss adds and what the pipeline's
        #: ``stage_aux`` carries (distributed/hybrid.py); None when dense
        self.aux_loss = None
        #: counts of the last forward that ride the same carry and leave
        #: the trainer's step as ``aux_stats`` (the drop-less layer's
        #: ``stats``; none otherwise)
        self.aux_stats = {}

    def forward(self, x):
        with annotate("blk/qkv"):
            h = self.ln_1(x)
        h = self.attn(h)
        with annotate("blk/attn_out"):
            if self.post_attn_norm is not None:
                h = self.post_attn_norm(h)
            x = x + h
        with annotate("blk/ffn"):
            if self.post_ffn_norm is not None:
                out = x + self.post_ffn_norm(self.mlp(self.ln_2(x)))
            else:
                out = x + self.mlp(self.ln_2(x))
        c = self.config
        if c.moe_dropless:
            # both terms are means over the layers, as HF's pooled
            # load-balance term and megablocks' batched losses are
            self.aux_loss = (
                c.moe_aux_weight * self.mlp.balance_loss
                + c.moe_z_weight * self.mlp.z_loss) * (1.0 / c.num_layers)
            self.aux_stats = self.mlp.stats
        elif c.moe_num_experts > 0:
            self.aux_loss = c.moe_aux_weight * self.mlp.aux_loss
        return out


class GPTEmbeddings(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.wte = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size,
            weight_attr=I.Normal(0.0, c.initializer_range))
        # under RoPE the positions live in the attention; there is no wpe
        self.wpe = nn.Embedding(
            c.max_seq_len, c.hidden_size,
            weight_attr=I.Normal(0.0, c.initializer_range)) \
            if c.position == "learned" else None
        self.dropout = c.dropout

    def forward(self, tokens):
        if self.wpe is None:
            x = self.wte(tokens)
        else:
            s = tokens.shape[1]
            pos = arange(0, s, dtype="int64").unsqueeze(0)
            x = self.wte(tokens) + self.wpe(pos)
        return F.dropout(x, self.dropout, training=self.training)


class GPT(nn.Layer):
    """Decoder-only GPT. ``forward`` returns logits; ``loss`` computes the
    shifted next-token cross entropy."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.blocks = nn.LayerList([GPTBlock(config)
                                    for _ in range(config.num_layers)])
        self.ln_f = _norm(config)
        if not config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                weight_attr=I.Normal(0.0, config.initializer_range),
                gather_output=True)
        if config.loop_steps > 1:
            # one Linear(h, 1) for all loop steps, on the final norm's output
            self.exit_gate = nn.Linear(
                config.hidden_size, 1,
                weight_attr=I.Normal(0.0, config.initializer_range))

    def forward(self, tokens):
        x = self.embeddings(tokens)
        if self.config.loop_steps > 1:
            x = self._looped(x)[0]
        else:
            for blk in self.blocks:
                x = blk(x)
            x = self.ln_f(x)
        if self.config.tie_word_embeddings:
            from ..tensor import matmul

            return matmul(x, self.embeddings.wte.weight, transpose_y=True)
        return self.lm_head(x)

    def _looped(self, x):
        """The stack run ``loop_steps`` times: the final norm after every
        step (its output starts the next), the exit gate there, and the
        exit rule's choice among the steps' states. Returns (the chosen
        state, the expected exit step, the chosen step)."""
        from ..tensor._helper import apply

        states, gates = [], []
        for _ in range(self.config.loop_steps):
            for blk in self.blocks:
                x = blk(x)
            x = self.ln_f(x)
            states.append(x)
            gates.append(self.exit_gate(x))
        n, thr = len(states), self.config.exit_threshold

        def rule(*sg):
            g = jax.nn.sigmoid(jnp.stack(sg[n:])[..., 0].astype(jnp.float32))
            return loop_exit(jnp.stack(sg[:n]), g, thr)

        return apply(rule, *states, *gates, name="loop_exit")

    def exit_steps(self, tokens):
        """(expected exit step, chosen exit step) [b, s] of a looped
        model's full forward over ``tokens``."""
        return self._looped(self.embeddings(tokens))[1:]

    # --- pipeline protocol (distributed/hybrid.py) -----------------------
    def pipeline_stem(self, tokens):
        return self.embeddings(tokens)

    def pipeline_blocks(self):
        return self.blocks

    def pipeline_head(self, x, tokens, labels=None):
        """Final norm + fused lm-head/CE (ops/fused_ce.py): the [B,S,V]
        logits never materialize in HBM. ``labels`` (eager .loss path):
        explicit targets instead of the shifted-token LM objective."""
        from ..ops.fused_ce import fused_linear_cross_entropy

        x = self.ln_f(x)
        # chunking over seq would fight an sp sharding; sp>1 runs one chunk
        chunk = None if _dctx.current_sequence_parallel() else 256
        lbl, next_token = (tokens, True) if labels is None \
            else (labels, False)
        if self.config.tie_word_embeddings:
            return fused_linear_cross_entropy(
                x, self.embeddings.wte.weight, lbl, chunk=chunk,
                next_token=next_token)
        return fused_linear_cross_entropy(
            x, self.lm_head.weight, lbl, chunk=chunk, transpose_w=True,
            next_token=next_token)

    # --- decoding (ops/decoding.py loops over the KV-cached forward) -----
    def generate(self, input_ids, max_new_tokens: int = 32,
                 decode_strategy: str = "greedy_search", top_k: int = 0,
                 top_p: float = 1.0, temperature: float = 1.0,
                 num_beams: int = 4, length_penalty: float = 0.0,
                 eos_token_id=None, seed: int = 0, paged: bool = False,
                 page_size: int = 0, kv_dtype=None):
        """Autoregressive generation with a preallocated KV cache, as one
        jitted program (prefill + lax.scan decode loop).

        decode_strategy: 'greedy_search' | 'sampling' | 'beam_search'
        (the paddlenlp generate() surface; the reference era only has
        host-side beam_search ops, beam_search_op.cc). Returns
        (ids [B, max_new_tokens], scores [B]).

        ``paged=True`` routes through the paged-KV serving engine
        (paddle_tpu.serving) instead of the dense [B, S_max] cache:
        same weights via the cached decode state, page-granular cache
        HBM, fixed-shape decode ticks. Greedy paged output is bitwise
        identical to the dense path (the wrapper picks a page size
        dividing prompt+max_new so the attention reduction length
        matches); sampling draws from per-request key chains, so paged
        sampling is reproducible but not token-identical to the dense
        shared-batch rng. Beam search has no paged path.

        ``kv_dtype`` (paged only): the page pool's storage dtype —
        None keeps the model dtype (the bitwise contract above);
        'bf16' halves and 'int8' quarters cache HBM per token, at
        which point greedy parity becomes a measured token-match rate
        (serving docs), not a bitwise guarantee.
        """
        import numpy as _np

        from ..framework.tensor import Tensor as _T
        from ..ops import decoding as D

        ids_v = input_ids._value if isinstance(input_ids, _T) else \
            jnp.asarray(input_ids)   # accepts np arrays AND jax tracers
        b, t0 = ids_v.shape
        smax = t0 + max_new_tokens
        if smax > self.config.max_seq_len:
            raise ValueError(
                f"prompt {t0} + max_new_tokens {max_new_tokens} exceeds "
                f"max_seq_len {self.config.max_seq_len}")
        if decode_strategy not in ("greedy_search", "sampling",
                                   "beam_search"):
            raise ValueError(f"unknown decode_strategy {decode_strategy!r}")
        if paged:
            if decode_strategy == "beam_search":
                raise NotImplementedError(
                    "paged decode supports greedy_search/sampling; beam "
                    "reordering needs per-beam page aliasing (ROADMAP)")
            return self._generate_paged(
                _np.asarray(ids_v), max_new_tokens, decode_strategy,
                top_k, top_p, temperature, eos_token_id, seed, page_size,
                kv_dtype)
        if kv_dtype is not None:
            raise ValueError("kv_dtype is a paged-cache knob; the dense "
                             "cache follows the model dtype (use "
                             "paged=True)")
        stacked, other = self._decode_state()
        cfg = self.config
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        L = cfg.num_layers * cfg.loop_steps     # a cache a (step, layer)
        dt = other["embeddings.wte.weight"].dtype

        # jit cache: retracing the whole prefill+scan program per call
        # would cost seconds per generate() in a serving loop. Bounded:
        # a serving workload feeds this an open-ended stream of
        # (batch, len) shapes, so LRU-cap it and count evictions
        # (cache_evict/gpt_gen_jit in the profiler registry).
        jkey = (b, t0, max_new_tokens, decode_strategy, top_k, top_p,
                temperature, num_beams, length_penalty, eos_token_id,
                str(dt))
        if "_gen_jit" not in self.__dict__:
            from ..utils.lru import LRUCache

            self.__dict__["_gen_jit"] = LRUCache(GPT.GEN_JIT_CACHE_SIZE,
                                                 "gpt_gen_jit")
        jit_cache = self.__dict__["_gen_jit"]
        run = jit_cache.get(jkey)
        if run is None:
            def run_fn(stacked, other, tokens, rng):
                n = tokens.shape[0]
                ck = jnp.zeros((n, L, smax, nh, hd), dt)
                cv = jnp.zeros((n, L, smax, nh, hd), dt)
                logits, ck, cv = gpt_cached_apply(
                    cfg, stacked, other, ck, cv, tokens, 0)

                def step(cache, tok, pos):
                    ck, cv = cache
                    lg, ck, cv = gpt_cached_apply(
                        cfg, stacked, other, ck, cv, tok[:, None], pos)
                    return lg, (ck, cv)

                if decode_strategy == "beam_search":
                    cache = D.tile_cache_for_beams((ck, cv), num_beams)
                    return D.beam_search_decode(
                        step, cache, logits, t0, max_new_tokens,
                        num_beams, length_penalty=length_penalty,
                        eos_token_id=eos_token_id)
                if decode_strategy == "sampling":
                    ids, _ = D.sampling_decode(
                        step, (ck, cv), logits, t0, max_new_tokens, rng,
                        top_k=top_k, top_p=top_p, temperature=temperature,
                        eos_token_id=eos_token_id)
                else:
                    ids, _ = D.greedy_decode(
                        step, (ck, cv), logits, t0, max_new_tokens,
                        eos_token_id=eos_token_id)
                return ids, jnp.zeros((n,), jnp.float32)

            run = jax.jit(run_fn)
            jit_cache[jkey] = run

        ids, scores = run(stacked, other, ids_v, jax.random.PRNGKey(seed))
        return _T(ids), _T(scores)

    #: LRU capacity for the per-shape generate() executables
    GEN_JIT_CACHE_SIZE = 16
    #: LRU capacity for cached paged serving engines (paged=True path)
    PAGED_ENGINE_CACHE_SIZE = 4

    def _generate_paged(self, ids_np, max_new_tokens, decode_strategy,
                        top_k, top_p, temperature, eos_token_id, seed,
                        page_size, kv_dtype=None):
        """generate() surface over the paged serving engine: one slot
        per batch row, slot capacity == the dense path's S_max (the
        wrapper picks the largest page size <= 16 dividing S_max, so
        greedy output stays bitwise-identical to the dense cache)."""
        import numpy as _np

        from ..framework.tensor import Tensor as _T
        from ..serving import ServingConfig, ServingEngine

        b, t0 = ids_np.shape
        smax = t0 + max_new_tokens
        ps = page_size
        if not ps:
            ps = next(p for p in (16, 8, 4, 2, 1) if smax % p == 0)
        if smax % ps:
            raise ValueError(
                f"page_size {ps} must divide prompt+max_new_tokens "
                f"{smax} for the paged generate() path (bitwise parity "
                "needs slot capacity == dense S_max)")
        strategy = "sampling" if decode_strategy == "sampling" else "greedy"
        ekey = (b, t0, max_new_tokens, ps, strategy, top_k, top_p,
                temperature, eos_token_id, kv_dtype)
        if "_paged_engines" not in self.__dict__:
            from ..utils.lru import LRUCache

            self.__dict__["_paged_engines"] = LRUCache(
                GPT.PAGED_ENGINE_CACHE_SIZE, "gpt_paged_engine")
        engines = self.__dict__["_paged_engines"]
        eng = engines.get(ekey)
        if eng is None or eng._stacked is not self._decode_state()[0]:
            eng = ServingEngine(self, ServingConfig(
                num_slots=b, page_size=ps, pages_per_slot=smax // ps,
                prefill_chunk=t0, decode=strategy,
                temperature=temperature, top_k=top_k, top_p=top_p,
                eos_token_id=eos_token_id, seed=seed,
                kv_dtype=kv_dtype))
            engines[ekey] = eng
        base = _np.asarray(jax.random.PRNGKey(seed))
        rids = [eng.submit(ids_np[i], max_new_tokens,
                           key=_np.asarray(jax.random.fold_in(base, i)))
                for i in range(b)]
        results = eng.run()
        out = _np.full((b, max_new_tokens),
                       eos_token_id if eos_token_id is not None else 0,
                       _np.int32)
        for i, rid in enumerate(rids):
            row = results[rid][:max_new_tokens]
            out[i, :row.shape[0]] = row
        eng.reset_results()
        return _T(jnp.asarray(out)), _T(jnp.zeros((b,), jnp.float32))

    # -- what ServingEngine asks of a model (models/tick.py) -------------
    def cache_spec(self) -> dict:
        """K and V a layer, and a loop step; a looped model's ticks hand out
        their exit steps, which ``LoopRecord`` keeps."""
        c = self.config
        spec = {"kind": "kv", "layers": c.num_layers * c.loop_steps,
                "heads": c.num_heads,
                "head_dim": c.hidden_size // c.num_heads,
                "loop_steps": c.loop_steps}
        if c.loop_steps > 1:
            spec["tick_record"] = LoopRecord
        return spec

    def ragged_apply(self, stacked, other, pools, tokens, tok_pos, tok_limit,
                     row_tab, row_pos0, row_len, sample_ix, *, decode_rows,
                     chunk_width, has_chunks=None):
        return gpt_ragged_apply(
            self.config, stacked, other, pools, tokens, tok_pos, tok_limit,
            row_tab, row_pos0, row_len, sample_ix, decode_rows, chunk_width,
            has_chunks=has_chunks)

    def _decode_state(self):
        """Cached (stacked, other) decode params; rebuilt only when the
        underlying param values changed (training step replaces them)."""
        token = id(self.embeddings.wte.weight._value)
        cached = self.__dict__.get("_gen_state")
        if cached is not None and cached[0] == token:
            return cached[1], cached[2]
        stacked, other = _gpt_decode_state(self)
        self.__dict__["_gen_state"] = (token, stacked, other)
        return stacked, other

    def publish_aux_stats(self, stats):
        """A training step's ``aux_stats`` (host values) into the
        profiler's registry: the trainer calls it after a profiled step."""
        from ..distributed.moe import publish_expert_load

        publish_expert_load(stats)

    def loss(self, tokens, labels=None):
        """Next-token LM loss (+ MoE load-balance aux when configured).
        labels default: tokens shifted left.

        Routes through the fused lm-head/CE (same kernel as
        pipeline_head): the [B, S, V] logits never materialize — the
        unfused forward()+cross_entropy spelling cost ~20% of the MoE
        bench step in f32 logit traffic (round-5 ablation)."""
        if self.config.loop_steps > 1:
            raise NotImplementedError(
                "training a looped stack (loop_steps > 1: a scan over steps "
                "around the layers, gradients summed over the steps, the "
                "exit-weighted loss) is not implemented; ROADMAP R1")
        x = self.embeddings(tokens)
        for blk in self.blocks:
            x = blk(x)
        loss = self.pipeline_head(x, tokens, labels=labels)
        if self.config.moe_num_experts > 0:
            for blk in self.blocks:
                loss = loss + blk.aux_loss
        return loss


def _require_served_block(cfg: GPTConfig):
    """The serving forwards below (gpt_block_body and what calls it:
    generate(), ServingEngine) run every dense block GPTBlock trains; what
    they still lack is refused here, by what it is. Experts inside a
    serving tick exist since ISSUE 37, in a model that brings its own
    forward (``models/dots3.py``: ``held_moe`` under ``blk/ffn``); *this*
    file's forwards still run dense FFNs alone."""
    lacks = []
    if cfg.moe_num_experts:
        lacks.append("an expert layer under blk/ffn of gpt_block_body "
                     "(dropless_moe or switch_moe in place of the dense "
                     "FFN, and the pools' page accounting unchanged)")
    if cfg.qk_norm:
        lacks.append("QK-norm in the served block")
    if lacks:
        raise NotImplementedError(
            "models/gpt.py's serving forwards and generate() run dense "
            "blocks (LayerNorm or RMSNorm, learned positions or RoPE, with "
            "or without biases, GELU or SwiGLU, sandwich norms, a looped "
            "stack); this model needs " + " and ".join(lacks)
            + ". A model that brings its own tick forward is served with "
            "its experts (models/dots3.py: held_moe inside the tick); "
            "GPTBlock's experts are not yet (ROADMAP R1)")


def _ln(x, w, b, eps):
    m = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) / jnp.sqrt(var + eps) * w + b


def _served_norm(cfg: GPTConfig, x, p, name: str):
    """The norm ``name`` of the parameters ``p``, of the model's kind."""
    if cfg.norm == "rmsnorm":
        return rms(x, p[name + ".weight"], cfg.layer_norm_eps)
    return _ln(x, p[name + ".weight"], p[name + ".bias"],
               cfg.layer_norm_eps)


def _linear(cfg: GPTConfig, x, p, name: str):
    y = x @ p[name + ".weight"]
    return y + p[name + ".bias"] if cfg.bias else y


def _residual(cfg: GPTConfig, xc, x, p, proj: str, post: str):
    """``xc`` plus the sub-layer's output projection of ``x``, through the
    sandwich norm ``post`` where the model has one. Without one the sums
    keep GPT-3's order, ``(xc + x W) + b``: its served program is pinned."""
    if cfg.sandwich_norm:
        return xc + _served_norm(cfg, _linear(cfg, x, p, proj), p, post)
    y = xc + x @ p[proj + ".weight"]
    return y + p[proj + ".bias"] if cfg.bias else y


def _served_head(cfg: GPTConfig, last, other):
    if cfg.tie_word_embeddings:
        return last @ other["embeddings.wte.weight"].T
    return last @ other["lm_head.weight"]


def _exit_gate(x, other):
    """The exit gate's sigmoid at ``x`` [..., h], float32 [...]."""
    w = other["exit_gate.weight"].astype(jnp.float32)[:, 0]
    b = other["exit_gate.bias"].astype(jnp.float32)[0]
    return jax.nn.sigmoid(x.astype(jnp.float32) @ w + b)


def gpt_block_body(cfg: GPTConfig, xc, p, attend, pos=None):
    """One pre-norm transformer block over stacked decode params ``p``,
    shared by the dense cached path (gpt_cached_apply) and the paged
    serving tick (gpt_ragged_apply) — the two must stay BITWISE
    identical, so the block math lives in exactly one place and only the
    cache handling differs: ``attend(q, kk, vv) -> (o [n,t,nh,hd],
    extra)`` writes this layer's KV into its cache and attends. Of ``cfg``
    it takes the block's kind (norm, position, bias, ffn, sandwich_norm)
    and sizes; ``pos`` (RoPE only) is each token's cache position,
    broadcastable to ``[n, t]``: q and k are rotated before ``attend``, so
    the cache holds rotated keys."""
    n, t = xc.shape[0], xc.shape[1]
    nh = cfg.num_heads
    h = cfg.hidden_size
    hd = h // nh
    # blk/* scope names (metadata only): one vocabulary with GPTBlock's
    # forward, read by the traced run's per-part metrics (PERF.md)
    with annotate("blk/qkv"):
        hn = _served_norm(cfg, xc, p, "ln_1")
        qkv = _linear(cfg, hn, p, "attn.qkv_proj")
        qkv = qkv.reshape(n, t, 3, nh, hd)
        q, kk, vv = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if cfg.position == "rope":
            q = rope_at(q, pos, cfg.rope_theta)
            kk = rope_at(kk, pos, cfg.rope_theta)
    with annotate("blk/attn"):
        o, extra = attend(q, kk, vv)
    with annotate("blk/attn_out"):
        xc = _residual(cfg, xc, o.reshape(n, t, h), p, "attn.out_proj",
                       "post_attn_norm")
    with annotate("blk/ffn"):
        h2 = _served_norm(cfg, xc, p, "ln_2")
        if cfg.ffn == "swiglu":
            mid = jax.nn.silu(_linear(cfg, h2, p, "mlp.fc_gate")) \
                * _linear(cfg, h2, p, "mlp.fc_in")
        else:
            mid = jax.nn.gelu(_linear(cfg, h2, p, "mlp.fc_in"),
                              approximate=True)
        xc = _residual(cfg, xc, mid, p, "mlp.fc_out", "post_ffn_norm")
    return xc, extra


def gpt_cached_apply(cfg: GPTConfig, stacked, other, ck, cv, tokens, pos0,
                     logits_index=None):
    """Pure-jax KV-cached forward for decoding (reference has no KV cache
    or generate() at all — its decoding is host-side beam_search ops,
    beam_search_op.cc; here decode is one compiled program).

    stacked: {block_suffix: [L, ...]} block params; other: {name: val};
    ck/cv: [N, L, S_max, NH, D] caches; tokens [N, T] processed at
    positions pos0..pos0+T. Returns (last-token logits [N, V], ck, cv).
    ``logits_index`` (may be traced): take logits at that query position
    instead of the last — the serving prefill pads prompts to a length
    bucket, so "last token" sits at true_len-1, not at T-1.

    Parity with GPT.forward is pinned by
    tests/test_generation.py::test_cached_prefill_matches_forward.

    A looped model (``cfg.loop_steps`` T > 1) keeps a cache for every
    (step, layer): ``ck``/``cv`` hold ``T * L`` cache layers, step-major,
    and the logits are read from the exit rule's step (``loop_exit``).
    """
    _require_served_block(cfg)
    n, t = tokens.shape
    hd = cfg.hidden_size // cfg.num_heads
    steps, nl = cfg.loop_steps, cfg.num_layers
    wte = other["embeddings.wte.weight"]
    pos = pos0 + jnp.arange(t)
    x = wte[tokens]
    if cfg.position == "learned":
        x = x + other["embeddings.wpe.weight"][pos][None]
    smax = ck.shape[2]
    key_pos = jnp.arange(smax)
    # causal-with-cache mask: query i sees cache positions <= pos0 + i
    mask = key_pos[None, None, None, :] <= \
        (pos0 + jnp.arange(t))[None, None, :, None]

    ckl = jnp.swapaxes(ck, 0, 1)            # [T * L, N, S, NH, D]
    cvl = jnp.swapaxes(cv, 0, 1)

    def block(xc, inp):
        p, k_c0, v_c0 = inp

        def attend(q, kk, vv):
            k_c = jax.lax.dynamic_update_slice(k_c0, kk, (0, pos0, 0, 0))
            v_c = jax.lax.dynamic_update_slice(v_c0, vv, (0, pos0, 0, 0))
            att = jnp.einsum("btnd,bsnd->bnts", q, k_c) / math.sqrt(hd)
            att = jnp.where(mask, att, -1e9)
            w = jax.nn.softmax(att.astype(jnp.float32),
                               axis=-1).astype(xc.dtype)
            return jnp.einsum("bnts,bsnd->btnd", w, v_c), (k_c, v_c)

        return gpt_block_body(cfg, xc, p, attend, pos)

    def sampled(x):
        if logits_index is None:
            return x[:, -1]
        return jax.lax.dynamic_index_in_dim(x, logits_index, axis=1,
                                            keepdims=False)

    if steps == 1:
        x, (ckl, cvl) = jax.lax.scan(block, x, (stacked, ckl, cvl))
        last = sampled(_served_norm(cfg, x, other, "ln_f"))
    else:
        def loop_step(xc, caches):
            xc, caches = jax.lax.scan(block, xc, (stacked,) + caches)
            with annotate("loop/exit"):
                xc = _served_norm(cfg, xc, other, "ln_f")
                state = sampled(xc)
            return xc, (caches, state)

        by_step = (steps, nl) + ckl.shape[1:]
        _, ((ckl, cvl), states) = jax.lax.scan(
            loop_step, x, (ckl.reshape(by_step), cvl.reshape(by_step)))
        ckl, cvl = (c.reshape((steps * nl,) + c.shape[2:])
                    for c in (ckl, cvl))
        with annotate("loop/exit"):
            last = loop_exit(states, _exit_gate(states, other),
                             cfg.exit_threshold)[0]
    logits = _served_head(cfg, last, other)
    return logits, jnp.swapaxes(ckl, 0, 1), jnp.swapaxes(cvl, 0, 1)


def gpt_ragged_apply(cfg: GPTConfig, stacked, other, pools, tokens,
                     tok_pos, tok_limit, row_tab, row_pos0, row_len,
                     sample_ix, decode_rows: int, chunk_width: int,
                     spec_k: int = 0, has_chunks=None):
    """Mixed prefill/decode forward over the PAGED cache: every token
    in flight rides one program. ``pools`` is the page pools
    (``serving.paged_cache.Pools``, stacked over layers) — this forward
    knows nothing of their format: each layer writes through
    ``pools.scatter(layer, ...)`` and reads through
    ``pools.attend(layer, ...)``. ``tokens``
    [NT] is the flat token buffer of one serving tick — ``decode_rows``
    resident decode tokens followed by the prefill chunks,
    ``chunk_width`` tokens each; which is which is *only* metadata:

    tok_pos    [NT] int32   absolute cache position of each token
    tok_limit  [NT] int32   first non-writable position of the token's
                            sequence — KV writes at ``tok_pos >=
                            tok_limit`` route to the null page (decode
                            rows: the slot capacity, so an
                            exact-capacity rider never stomps its own
                            published tail page; prefill rows: the
                            true prompt length, so chunk padding never
                            lands in a page a neighbour aliases; pad
                            rows: 0)
    row_tab    [R, NPs]     page-table row per ragged attention row,
                            R = decode_rows + num_chunks (pad chunk
                            rows: all-null tables)
    row_pos0   [R] int32    first query position of each row
    row_len    [R] int32    real queries per row (decode rows: 1)
    sample_ix  [S] int32    flat indices whose final hidden states
                            feed the logits head (one per emitter)
    has_chunks bool scalar  optional: False on a tick none of whose
                            chunk rows is real (all pad). The chunk
                            rows' attention then runs under a ``cond``
                            INSIDE the block that only reads the pools
                            and returns ``[nch, w, NH, D]`` (zeros when
                            skipped: nothing samples a pad row). On the
                            v5e such a tick is 0.8 ms of 17.7 shorter
                            and the compiled tick still holds no
                            pool-sized temporary (PERF.md section 6,
                            PR 32). Everything else of the tick is one
                            body whatever the mix

    Hidden-state compute (embeddings, LN, QKV/MLP matmuls) runs once
    over the flat buffer; each token's KV is scattered to its own
    page/offset; attention routes through the ONE
    ``ragged_paged_attention`` entry point, with rows grouped by their
    static query width — decode rows as ``[decode_rows, 1]`` and chunk
    rows as ``[num_chunks, chunk_width]`` — so the decode rows pay
    the decode gather cost, not ``chunk_width×`` pad
    queries ("Ragged Paged Attention", PAPERS.md: per-row
    ``(pos0, true_len)`` metadata; the width grouping is the XLA-
    friendly layout of the same raggedness, and the Pallas kernel
    underneath handles either width in one grid). All metadata may be
    traced: one compiled program serves every mix of resident decodes
    and prompt chunks. Returns (logits [S, V], pools, aux): ``aux`` what the
    tick says of itself (``models/tick.py``), of this model nothing, ``{}``,
    unless it is looped (below).

    ``spec_k > 0`` (speculative decoding, serving/spec.py) widens each
    of the ``decode_rows`` slot rows into a **verify row** of
    ``1 + spec_k`` tokens: the flat buffer becomes ``decode_rows`` last
    tokens, then ``decode_rows * spec_k`` draft tokens (slot-major),
    then the chunks. The slot rows' attention groups as
    ``[decode_rows, 1 + spec_k]``; logits can be sampled at EVERY
    verify position (a verify row is exactly a chunk-shaped row whose
    logits are kept per position, not just at the end). A slot that is
    not speculating this tick rides the same group with
    ``row_len == 1`` — its draft positions are pad queries
    (``tok_limit == 0`` routes their KV writes to the null page).

    Bitwise contract (the engine's parity tests rest on it):
    per-token results are independent of which *other* rows share the
    program — hidden/head contractions are row-independent, LN/GELU
    are elementwise, and attention always reduces over the full slot
    capacity with exact-zero masked weights (``ops/paged_attention._
    gather_attend``, the one shared spelling) — so a decode row equals
    a chunk row of length 1 at the same position, token for token, bit
    for bit, and a verify position equals the decode row the
    non-speculative engine would have run at that position.

    With int8 pools (ISSUE 12) every token's KV write quantizes at the
    page's running-max scale and the attention gather dequantizes with
    the same scales (``ops/paged_attention.paged_kv_scatter`` and
    ``_gather_attend``, behind ``pools``); numerics are then tolerance,
    not bitwise, vs the unquantized pool (the engine only asserts
    bitwise between two int8 engines).

    How the pools travel is decided here and nowhere else (ROADMAP
    S3): they are the CARRY of the ``lax.scan`` over ``(stacked,
    arange(L))``, beside ``x``; a step hands the block the whole stacks
    and its layer's index, no step slices a layer out or writes one
    back, and XLA updates the (donated) stacks in place. Every token's
    write lands before its layer's read: the block's ``attend`` scatters
    first and reads the stacks the scatter returned.

    A looped model (``cfg.loop_steps`` T > 1, ``pools`` of ``T * L`` cache
    layers) runs that scan inside a scan over ``arange(T)`` with ``(x,
    pools)`` as its carry too: step ``t``'s layer ``l`` writes and reads
    cache layer ``t * L + l``, the weights are closed over once, each step
    ends in the final norm (its output starts the next) and hands out its
    state at the sampled rows. The head reads the exit rule's step
    (``loop_exit``), and ``aux["exit_steps"]`` is the expected and the
    chosen exit step of each sampled row, float32 ``[2, S]``.
    """
    _require_served_block(cfg)
    nt = tokens.shape[0]
    nd = decode_rows
    base = nd * (1 + spec_k)
    nch = (nt - base) // chunk_width if chunk_width else 0
    nh = cfg.num_heads
    hd = cfg.hidden_size // nh
    steps, nl = cfg.loop_steps, cfg.num_layers
    ps = pools.page_size
    nps = row_tab.shape[1]
    wte = other["embeddings.wte.weight"]
    with annotate("tick/embed"):
        x = wte[tokens[:, None]]                            # [NT, 1, h]
        if cfg.position == "learned":
            x = x + other["embeddings.wpe.weight"][tok_pos[:, None]]
    # token -> ragged row (static: the flat layout never changes);
    # draft tokens share their slot's row (same page table)
    parts = [jnp.arange(nd, dtype=jnp.int32)]
    if spec_k:
        parts.append(jnp.repeat(jnp.arange(nd, dtype=jnp.int32), spec_k))
    if nch:
        parts.append(jnp.repeat(nd + jnp.arange(nch, dtype=jnp.int32),
                                chunk_width))
    tok_row = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    # write targets: real positions go to their slot page, everything
    # at/past the limit to the null page (clip keeps the page-table
    # index in range for positions past the slot capacity)
    page = jnp.where(
        tok_pos < tok_limit,
        row_tab[tok_row, jnp.minimum(tok_pos // ps, nps - 1)],
        0)
    off = tok_pos % ps

    def stack(x, pools, first):
        """The ``L`` layers once over ``(x, pools)``, layer ``l`` on cache
        layer ``first + l``."""

        def block(carry, inp):
            xc, pl0 = carry
            p, layer = inp

            def attend(q, kk, vv):
                with annotate("blk/kv_scatter"):
                    pl = pl0.scatter(layer, page, off, kk, vv)
                outs = []
                if nd and spec_k:
                    # verify grouping [nd, 1 + spec_k]: each slot's last
                    # token plus its drafts as one chunk-shaped row; the
                    # outputs un-interleave back into flat-buffer order
                    qv = jnp.concatenate(
                        [q[:nd], q[nd:base, 0].reshape(nd, spec_k, nh, hd)],
                        axis=1)
                    ov = pl.attend(layer, qv, row_tab[:nd], row_pos0[:nd],
                                   row_len[:nd])
                    outs.append(ov[:, :1])
                    outs.append(ov[:, 1:].reshape(nd * spec_k, 1, nh, hd))
                elif nd:
                    outs.append(pl.attend(layer, q[:nd], row_tab[:nd],
                                          row_pos0[:nd], row_len[:nd]))
                if nch:
                    qp = q[base:, 0].reshape(nch, chunk_width, nh, hd)

                    def chunk_rows():
                        return pl.attend(layer, qp, row_tab[nd:],
                                         row_pos0[nd:], row_len[nd:])

                    if has_chunks is None:
                        op = chunk_rows()
                    else:
                        op = jax.lax.cond(has_chunks, chunk_rows,
                                          lambda: jnp.zeros_like(qp))
                    outs.append(op.reshape(nch * chunk_width, 1, nh, hd))
                o = outs[0] if len(outs) == 1 else \
                    jnp.concatenate(outs, axis=0)
                return o, pl

            return gpt_block_body(cfg, xc, p, attend, tok_pos[:, None]), None

        layers = jnp.arange(nl, dtype=jnp.int32)
        if first is not None:   # None: one pass, the pinned GPT-3 program
            layers = first + layers
        return jax.lax.scan(block, (x, pools), (stacked, layers))[0]

    if steps == 1:
        x, pools = stack(x, pools, None)
        with annotate("tick/head"):
            x = _served_norm(cfg, x, other, "ln_f")
            logits = _served_head(cfg, x[sample_ix, 0], other)  # [S, V]
        return logits, pools, {}

    def loop_step(carry, step):
        xc, pl = stack(*carry, step * nl)
        with annotate("loop/exit"):
            xc = _served_norm(cfg, xc, other, "ln_f")
            state = xc[sample_ix, 0]                        # [S, h]
        return (xc, pl), state

    (x, pools), states = jax.lax.scan(
        loop_step, (x, pools), jnp.arange(steps, dtype=jnp.int32))
    with annotate("loop/exit"):
        last, expected, chosen = loop_exit(
            states, _exit_gate(states, other), cfg.exit_threshold)
    with annotate("tick/head"):
        logits = _served_head(cfg, last, other)
    return logits, pools, {"exit_steps": jnp.stack([expected, chosen])}


def _gpt_decode_state(model: "GPT"):
    """(stacked {sfx: [L, ...]}, other {name: val}) jnp dicts from the
    eager model, for gpt_cached_apply. A model built under ``LazyGuard``
    has no weights yet: its state is drawn here, once, straight into the
    stacks (``_decode_state_drawer``)."""
    from ..framework.lazy import is_abstract
    from ..static.functional import state_tensors

    _require_served_block(model.config)
    blocks = list(model.blocks)
    sfx, t0 = state_tensors(blocks[0])[:2]
    per_block = [state_tensors(b)[1] for b in blocks]   # one walk per block
    pn, pt, _, _ = state_tensors(model)
    block_ids = {id(x) for pb in per_block for x in pb}
    rest = [(n, p) for n, p in zip(pn, pt) if id(p) not in block_ids]
    if any(is_abstract(p) for p in pt):
        from ..core import rng

        # one jitted call, seeded by the global generator's next key
        t_draw = time.perf_counter()
        state = jax.jit(_decode_state_drawer(sfx, t0, len(blocks), rest))(
            rng.next_key())
        # the host's seconds: tracing, compiling and queueing the draw;
        # the device's are waited for by whoever first needs the weights
        _ptrace.charge_setup(
            "weights", time.perf_counter() - t_draw,
            sum(a.nbytes for a in jax.tree_util.tree_leaves(state)),
            where="device")
        return state
    stacked = {s: jnp.stack([pb[j]._value for pb in per_block], 0)
               for j, s in enumerate(sfx)}
    return stacked, {n: p._value for n, p in rest}


def _decode_state_drawer(sfx, block_params, num_layers: int, rest):
    """``key -> (stacked, other)`` for a model whose parameters are
    ``LazyGuard``'s placeholders: a scan over the layers draws each from its
    parameters' recorded initializers, in their own type, into its row of
    the stacks, so under ``jit`` the device never holds more than the state
    and one layer's float32 staging, and the model itself stays abstract
    (ROADMAP S16's serving half). ``block_params`` are one block's
    parameters: the blocks of a stack are built alike."""
    def draw(params, key):
        return [p._lazy_initializer(p._value.shape, p._value.dtype,
                                    jax.random.fold_in(key, j))
                for j, p in enumerate(params)]

    def drawer(key):
        layers_key, rest_key = jax.random.split(key)
        _, stacks = jax.lax.scan(
            lambda c, k: (c, draw(block_params, k)), None,
            jax.random.split(layers_key, num_layers))
        return dict(zip(sfx, stacks)), \
            dict(zip([n for n, _ in rest],
                     draw([p for _, p in rest], rest_key)))

    return drawer


class GPTForGeneration(nn.Layer):
    """Export wrapper: forward(tokens) runs the full generate loop, so
    ``paddle_tpu.jit.save`` serializes prefill + KV-cached decode as ONE
    jax.export artifact runnable in a fresh process (the reference's
    save_inference_model + beam-search-ops analogue, done compiler-side)."""

    def __init__(self, gpt: GPT, max_new_tokens: int = 16,
                 decode_strategy: str = "greedy_search", **gen_kw):
        super().__init__()
        self.gpt = gpt
        self.max_new_tokens = max_new_tokens
        self.decode_strategy = decode_strategy
        self.gen_kw = gen_kw

    def forward(self, tokens):
        ids, _ = self.gpt.generate(tokens,
                                   max_new_tokens=self.max_new_tokens,
                                   decode_strategy=self.decode_strategy,
                                   **self.gen_kw)
        return ids


def gpt_tiny(**kw):
    """Small config for tests/dryrun."""
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                    num_heads=4, max_seq_len=64, **kw)
    return GPT(cfg)
