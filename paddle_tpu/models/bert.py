"""BERT model family — the DP+AMP north-star config (BASELINE.md:
"BERT-base pretraining, DP + AMP(bf16), tokens/sec/chip + loss curve").

The reference has no BERT in-tree (its BERT runs were user model code over
nn.TransformerEncoder, reference python/paddle/nn/layer/transformer.py);
here it is first-class and TPU-first, mirroring the GPT design
(models/gpt.py): tensor-parallel projections carrying PartitionSpecs,
identical block structure per layer (stackable for lax.scan / pipeline),
flash attention for the bidirectional self-attention when no padding mask
is supplied.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from jax.sharding import PartitionSpec as P

from .. import nn
from ..distributed.parallel_layers import (ColumnParallelLinear,
                                           RowParallelLinear,
                                           VocabParallelEmbedding)
from ..nn import functional as F
from ..nn import initializer as I
from ..tensor import arange


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden_size: int = 0            # default 4*hidden
    max_seq_len: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.0
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02
    # MLM head gather width: project only the top-`max_predictions`
    # masked positions onto the vocab instead of the full sequence
    # (reference: create_pretraining_data's masked_lm_positions arrays,
    # max_predictions_per_seq=80 at seq 512 — the reference NEVER runs
    # the vocab projection on unmasked positions either; its data
    # pipeline materializes the gather). 0 = full-sequence head.
    max_predictions: int = 0

    def __post_init__(self):
        if not self.ffn_hidden_size:
            self.ffn_hidden_size = 4 * self.hidden_size

    @staticmethod
    def bert_base():
        return BertConfig()

    @staticmethod
    def bert_large():
        return BertConfig(hidden_size=1024, num_layers=24, num_heads=16)

    def num_params(self) -> int:
        h, L = self.hidden_size, self.num_layers
        per_block = 4 * h * h + 2 * h * self.ffn_hidden_size + 13 * h
        emb = (self.vocab_size + self.max_seq_len +
               self.type_vocab_size) * h
        return emb + L * per_block + 2 * h

    def flops_per_token(self, seq_len=None) -> float:
        s = seq_len or self.max_seq_len
        return 6.0 * self.num_params() + 12.0 * self.num_layers * \
            self.hidden_size * s


class BertSelfAttention(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        c = config
        init = I.Normal(0.0, c.initializer_range)
        self.num_heads = c.num_heads
        self.head_dim = c.hidden_size // c.num_heads
        self.qkv_proj = ColumnParallelLinear(
            c.hidden_size, 3 * c.hidden_size, weight_attr=init,
            gather_output=False)
        self.qkv_proj.param_shardings = {"weight": P(None, "tp"),
                                         "bias": P("tp")}
        self.out_proj = RowParallelLinear(c.hidden_size, c.hidden_size,
                                          weight_attr=init)
        self.dropout = c.dropout

    def forward(self, x, attn_mask=None):
        b, s, h = x.shape[0], x.shape[1], x.shape[2]
        qkv = self.qkv_proj(x)
        qkv = qkv.reshape([b, s, 3, self.num_heads, self.head_dim])
        q, k, v = qkv.unbind(2)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=False,
            dropout_p=self.dropout, training=self.training)
        return self.out_proj(out.reshape([b, s, h]))


class BertBlock(nn.Layer):
    """Post-norm encoder block (BERT convention); identical structure per
    layer so the compiled path can stack params."""

    def __init__(self, config: BertConfig):
        super().__init__()
        c = config
        init = I.Normal(0.0, c.initializer_range)
        self.attn = BertSelfAttention(c)
        self.ln_1 = nn.LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.fc_in = ColumnParallelLinear(c.hidden_size, c.ffn_hidden_size,
                                          weight_attr=init,
                                          gather_output=False)
        self.fc_out = RowParallelLinear(c.ffn_hidden_size, c.hidden_size,
                                        weight_attr=init)
        self.ln_2 = nn.LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.dropout = c.dropout

    def forward(self, x, attn_mask=None):
        a = self.attn(x, attn_mask)
        x = self.ln_1(x + F.dropout(a, self.dropout,
                                    training=self.training))
        m = self.fc_out(F.gelu(self.fc_in(x)))
        return self.ln_2(x + F.dropout(m, self.dropout,
                                       training=self.training))


class BertEmbeddings(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        c = config
        init = I.Normal(0.0, c.initializer_range)
        self.word = VocabParallelEmbedding(c.vocab_size, c.hidden_size,
                                           weight_attr=init)
        self.position = nn.Embedding(c.max_seq_len, c.hidden_size,
                                     weight_attr=init)
        self.token_type = nn.Embedding(c.type_vocab_size, c.hidden_size,
                                       weight_attr=init)
        self.ln = nn.LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.dropout = c.dropout

    def forward(self, tokens, token_type_ids=None):
        s = tokens.shape[1]
        pos = arange(0, s, dtype="int64").unsqueeze(0)
        x = self.word(tokens) + self.position(pos)
        if token_type_ids is not None:
            x = x + self.token_type(token_type_ids)
        return F.dropout(self.ln(x), self.dropout, training=self.training)


class Bert(nn.Layer):
    """Encoder stack; returns (sequence_output, pooled_output)."""

    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.embeddings = BertEmbeddings(config)
        self.blocks = nn.LayerList([BertBlock(config)
                                    for _ in range(config.num_layers)])
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)

    def forward(self, tokens, token_type_ids=None, attn_mask=None):
        x = self.embeddings(tokens, token_type_ids)
        for blk in self.blocks:
            x = blk(x, attn_mask)
        from ..tensor import tanh

        pooled = tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForPretraining(nn.Layer):
    """MLM + NSP heads (the BERT pretraining objective). ``loss`` takes
    (tokens, token_type_ids, mlm_labels, nsp_labels); mlm_labels use -100
    for unmasked positions (ignored)."""

    def __init__(self, config: BertConfig):
        super().__init__()
        c = config
        self.config = c
        self.bert = Bert(c)
        self.mlm_transform = nn.Linear(c.hidden_size, c.hidden_size)
        self.mlm_ln = nn.LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.mlm_bias = self.create_parameter(
            [c.vocab_size], default_initializer=I.Constant(0.0))
        self.nsp_head = nn.Linear(c.hidden_size, 2)

    def forward(self, tokens, token_type_ids=None, attn_mask=None):
        seq, pooled = self.bert(tokens, token_type_ids, attn_mask)
        from ..tensor import matmul

        h = self.mlm_ln(F.gelu(self.mlm_transform(seq)))
        # tied decoder: project onto the word-embedding matrix
        mlm_logits = matmul(h, self.bert.embeddings.word.weight,
                            transpose_y=True) + self.mlm_bias
        nsp_logits = self.nsp_head(pooled)
        return mlm_logits, nsp_logits

    # --- pipeline protocol (distributed/hybrid.py) -----------------------
    def pipeline_stem(self, tokens, token_type_ids, mlm_labels, nsp_labels):
        return self.bert.embeddings(tokens, token_type_ids)

    def pipeline_blocks(self):
        return self.bert.blocks

    def pipeline_head(self, x, tokens, token_type_ids, mlm_labels,
                      nsp_labels):
        """MLM via the fused tied-decoder CE + NSP on the pooled output.

        With ``config.max_predictions`` set, the masked positions are
        gathered FIRST (top_k on the mask — jittable, static shapes) and
        only those run the transform + vocab projection: at a 15% mask
        rate this removes ~85% of the head flops, exactly like the
        reference's masked_lm_positions pipeline. Equal to the
        full-sequence ignore-index CE whenever no row has more than
        max_predictions masked positions (excess positions are dropped,
        mirroring the reference data generator's truncation)."""
        (mlm, _), (nsp, _) = self.pipeline_head_terms(
            x, tokens, token_type_ids, mlm_labels, nsp_labels)
        return mlm + nsp

    def pipeline_head_terms(self, x, tokens, token_type_ids, mlm_labels,
                            nsp_labels):
        """``pipeline_head``'s two means, each with the count it is taken
        over: ``((mlm, masked positions kept), (nsp, rows))``. A caller
        that runs the head on slices of the batch (the pipeline's stages
        share it, distributed/pipeline.py) weighs each slice's means by
        these, since slices differ in their masked positions."""
        from ..distributed import context as _dctx
        from ..ops.fused_ce import fused_linear_cross_entropy
        from ..tensor import take_along_axis, tanh, topk, where
        from ..tensor.creation import full_like

        cls = x[:, 0]                    # CLS BEFORE any gather: NSP must
        maxp = int(getattr(self.config, "max_predictions", 0) or 0)
        if maxp and maxp < int(mlm_labels.shape[1]):
            is_masked = (mlm_labels != -100).astype("int32")
            score, pos = topk(is_masked, maxp, axis=1)
            x = take_along_axis(x, pos.unsqueeze(-1), axis=1)
            mlm_labels = where(score > 0,
                               take_along_axis(mlm_labels, pos, axis=1),
                               full_like(score, -100))
        h = self.mlm_ln(F.gelu(self.mlm_transform(x)))
        chunk = None if _dctx.current_sequence_parallel() else 256
        mlm = fused_linear_cross_entropy(
            h, self.bert.embeddings.word.weight, mlm_labels,
            bias=self.mlm_bias, chunk=chunk)
        pooled = tanh(self.bert.pooler(cls))
        nsp = F.cross_entropy(self.nsp_head(pooled).astype("float32"),
                              nsp_labels)
        return ((mlm, (mlm_labels != -100).astype("int32").sum()),
                (nsp, int(nsp_labels.shape[0])))

    def loss(self, tokens, token_type_ids, mlm_labels, nsp_labels):
        """Same objective as pipeline_head (fused tied-decoder CE +
        masked-position gather): the [B, S, V] logits never materialize
        here either."""
        x = self.bert.embeddings(tokens, token_type_ids)
        for blk in self.bert.blocks:
            x = blk(x)
        return self.pipeline_head(x, tokens, token_type_ids, mlm_labels,
                                  nsp_labels)


def bert_tiny(**kw):
    """Small config for tests."""
    cfg = BertConfig(vocab_size=128, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=64, type_vocab_size=2, **kw)
    return BertForPretraining(cfg)
