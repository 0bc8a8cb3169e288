"""Fused lm-head + softmax cross-entropy (chunked, logits never in HBM).

The reference fuses softmax+CE in one CUDA kernel
(reference paddle/fluid/operators/softmax_with_cross_entropy_op.cu) but
still materializes the full [tokens, vocab] logits produced by the
preceding matmul. On TPU the HBM traffic of those logits dominates the
loss computation for LM-scale vocabularies (batch 8 × seq 1024 × vocab
32768 in f32 is >1 GB per direction), so here the *projection itself* is
fused into the loss:

  - ``lax.scan`` over sequence chunks; each chunk computes its logits
    tile ``x_chunk @ W^T`` (f32 MXU accumulation), reduces it to
    logsumexp + the gold-label logit, and discards it — peak logits
    footprint is one chunk, not the full sequence.
  - the function is a ``jax.custom_vjp``, and jax picks the rule by
    whether the call is being differentiated. Not differentiated
    (evaluation): the loss-only scan, nothing saved. Differentiated
    (every trainer's ``value_and_grad``, the eager tape's ``jax.vjp``):
    the chunk that makes a logits tile makes its gradients from it —
    ``d = (softmax - onehot) * mask / n`` (``n``, the count of kept
    labels, is known before the scan), ``dx_chunk = d @ W`` and
    ``dW += x_chunk^T @ d`` as the scan's carry — so a step multiplies
    by the vocabulary three times (logits, dx, dW), not four: no tile is
    made a second time in the backward pass, which only scales the saved
    ``dx`` and ``dW`` (the size of ``x`` and of ``W``) by the incoming
    cotangent. Which rule a program compiled is counted at trace time in
    ``head/fused_ce_traces{rule=grad_in_forward|loss_only}``.
  - plain jax throughout (a Mosaic call cannot be partitioned): under
    GSPMD a tp-sharded vocab axis turns the max, the sum-exp and the gold
    logit into tp reductions and ``dx`` into an all-reduce, ``dW`` stays
    local to its vocabulary shard.
  - the rule is first order: ``jax.grad`` of ``jax.grad`` differentiates
    the forward rule's own arithmetic and is right, forward mode
    (``jax.jvp``, ``jax.hessian``'s outer pass) is refused by jax in words.

Used by the models' loss heads (``GPT.pipeline_head``, which the hybrid
trainer runs for GPT-3 and OLMoE, ``SolarOpen2.pipeline_head``, BERT's
tied MLM decoder) and exposed as
``paddle_tpu.nn.functional.fused_linear_cross_entropy``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..tensor._helper import apply

IGNORE = -100
_F32 = jnp.float32


def _count_trace(rule):
    from ..profiler import metrics

    metrics.registry().counter(
        "head/fused_ce_traces{rule=%s}" % rule).add(1)


def _chunked(x, labels, chunk):
    """x [B, S, H], labels [B, S] -> [nc, B, cs, H], [nc, B, cs]."""
    b, s, h = x.shape
    if chunk is None or chunk >= s:
        nc, cs = 1, s
    else:
        cs = chunk
        while s % cs:            # shrink to a divisor (seq is 128-aligned)
            cs //= 2
        nc = s // cs
    return (x.reshape(b, nc, cs, h).transpose(1, 0, 2, 3),
            labels.reshape(b, nc, cs).transpose(1, 0, 2))


def _kept(labels, ignore_index):
    """(1 / count of kept labels, at least 1) as f32: the mean's weight,
    a reduction over ``labels`` known before the scan."""
    n = jnp.sum((labels != ignore_index).astype(jnp.int32))
    return 1.0 / jnp.maximum(n, 1).astype(_F32)


def _tile(xc, lc, w, bias, ignore_index, w_is_vh):
    """One chunk's logits tile, made once: xc [B, cs, H], lc [B, cs] ->
    (logits [B, cs, V] f32, lse [B, cs], onehot [B, cs, V], mask [B, cs],
    the chunk's summed loss)."""
    v = w.shape[0] if w_is_vh else w.shape[1]
    # contract H: w is [V, H] (embedding layout) or [H, V]
    logits = jax.lax.dot_general(
        xc, w, (((2,), (1 if w_is_vh else 0,)), ((), ())),
        preferred_element_type=_F32)                          # [B, cs, V]
    if bias is not None:
        logits = logits + bias.astype(_F32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    mask = lc != ignore_index
    safe = jnp.clip(lc, 0, v - 1)
    # gold logit via one-hot contraction, not take_along_axis: XLA
    # fuses it to a select+reduce (no [B,cs,V] materialization), and —
    # load-bearing — GSPMD partitions it cleanly when V is tp-sharded
    # and the batch dp-sharded inside a manual-pp shard_map region,
    # where the equivalent gather crashes the SPMD partitioner
    # (spmd_partitioner_util.cc partition-group check).
    onehot = jax.nn.one_hot(safe, v, dtype=logits.dtype)
    gold = jnp.einsum("bsv,bsv->bs", logits, onehot)
    loss = jnp.sum(jnp.where(mask, lse - gold, 0.0))
    return logits, lse, onehot, mask, loss


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_ce(x, w, bias, labels, ignore_index, chunk, w_is_vh):
    """x: [B, S, H]; w: [V, H] (embedding layout) or [H, V]; bias: None or
    [V], added to the logits (e.g. BERT's tied MLM decoder); labels [B, S].

    Returns mean CE over non-ignored positions, f32 scalar.
    """
    _count_trace("loss_only")

    def body(total, inp):
        return total + _tile(*inp, w, bias, ignore_index, w_is_vh)[-1], None

    total, _ = jax.lax.scan(body, jnp.zeros((), _F32),
                            _chunked(x, labels, chunk))
    return total * _kept(labels, ignore_index)


def _fused_ce_fwd(x, w, bias, labels, ignore_index, chunk, w_is_vh):
    """The loss, and as residuals its gradients at cotangent 1: every
    chunk's ``d`` is made from the tile its loss was made from."""
    _count_trace("grad_in_forward")
    inv_n = _kept(labels, ignore_index)

    def body(carry, inp):
        xc, lc = inp
        logits, lse, onehot, mask, loss = _tile(
            xc, lc, w, bias, ignore_index, w_is_vh)
        # float32 into both products, as autodiff's cotangent of the
        # logits was (the MXU takes it as bf16 at default precision)
        d = (jnp.exp(logits - lse[..., None]) - onehot) * jnp.where(
            mask, inv_n, 0.0)[..., None]                      # [B, cs, V]
        dxc = jax.lax.dot_general(
            d, w, (((2,), (0 if w_is_vh else 1,)), ((), ())),
            preferred_element_type=_F32).astype(xc.dtype)     # [B, cs, H]
        # contract the chunk's positions, in w's own layout; the running
        # sum is the carry, so XLA adds into it in the product's fusion
        lhs, rhs = (d, xc) if w_is_vh else (xc, d)
        dwc = jax.lax.dot_general(
            lhs, rhs, (((0, 1), (0, 1)), ((), ())),
            preferred_element_type=_F32).astype(w.dtype)
        total, dw, db = carry
        if bias is not None:
            db = db + jnp.sum(d, axis=(0, 1))
        return (total + loss, dw + dwc, db), dxc

    db0 = None if bias is None else jnp.zeros(bias.shape, _F32)
    (total, dw, db), dxs = jax.lax.scan(
        body, (jnp.zeros((), _F32), jnp.zeros_like(w), db0),
        _chunked(x, labels, chunk))
    dx = dxs.transpose(1, 0, 2, 3).reshape(x.shape)
    if bias is not None:
        db = db.astype(bias.dtype)
    return total * inv_n, (dx, dw, db)


def _fused_ce_bwd(ignore_index, chunk, w_is_vh, res, g):
    """Scale the saved gradients by the loss's cotangent (1.0 from
    ``value_and_grad``, a loss scale under amp); labels get none."""
    dx, dw, db = (None if r is None else (r * g).astype(r.dtype)
                  for r in res)
    return dx, dw, db, None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_linear_cross_entropy_fn(x, w, labels, ignore_index=IGNORE,
                                  chunk=256, transpose_w=False, bias=None):
    """Pure-jax entry (used inside jitted trainers).

    ``transpose_w=False``: w is [V, H] (tied-embedding layout, logits =
    x @ w.T). ``transpose_w=True``: w is [H, V] (Linear layout).
    """
    return _fused_ce(x, w, bias, labels, ignore_index, chunk,
                     not transpose_w)


def shifted_labels(tokens, ignore_index=IGNORE):
    """Next-token labels: tokens shifted left, last position ignored."""
    return jnp.concatenate(
        [tokens[:, 1:],
         jnp.full((tokens.shape[0], 1), ignore_index, tokens.dtype)], axis=1)


def fused_linear_cross_entropy(x, weight, labels, ignore_index=IGNORE,
                               chunk=256, transpose_w=False, bias=None,
                               next_token=False, name=None):
    """Tape-level entry (Tensor in/out). ``next_token=True`` shifts the
    labels left by one (LM objective) before the loss."""
    def f(xv, wv, lv, *rest):
        if next_token:
            lv = shifted_labels(lv, ignore_index)
        return fused_linear_cross_entropy_fn(
            xv, wv, lv, ignore_index=ignore_index, chunk=chunk,
            transpose_w=transpose_w, bias=rest[0] if rest else None)

    args = (x, weight, labels) + ((bias,) if bias is not None else ())
    return apply(f, *args, name="fused_linear_cross_entropy")
