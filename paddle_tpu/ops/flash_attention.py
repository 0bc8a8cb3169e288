"""Flash attention — hand-tiled Pallas TPU kernel, fwd + bwd.

New capability vs the reference: the reference has no fused *training*
attention at all (SURVEY.md §5 "Long-context" — only the inference-side
multihead_matmul op, reference operators/fused/multihead_matmul_op.cu built by
framework/ir/multihead_matmul_fuse_pass.cc). Training attention there is an
unfused python composition over matmul/softmax kernels
(python/paddle/nn/layer/transformer.py). On TPU the attention kernel is the
MFU make-or-break (SURVEY.md §7 "Hard parts"), so it is first-class here:

  - forward: online-softmax (flash) tiling. Grid (batch·heads, q_blocks,
    k_blocks); the k dimension is the innermost ("arbitrary") axis so the
    running max / denominator / accumulator live in VMEM scratch across k
    steps. Logits never materialize in HBM: O(S) memory instead of O(S^2).
  - backward: recompute-based flash backward as two kernels — one accumulates
    dQ (grid over q blocks), one accumulates dK/dV (grid over k blocks) —
    using the saved logsumexp and the precomputed row dot delta = sum(dO·O).
  - causal masking: fully-masked tiles skip all compute (the MXU never sees
    them) and tiles below the diagonal skip mask evaluation. Dead-tile K/V
    DMA is elided by clamping the K-block index map to the diagonal
    (``lax.min(j, i)``): Pallas only issues a copy when a block index
    changes between grid steps, so once the k index saturates at the
    diagonal no further HBM traffic happens for that q row — causal
    attention reads half the K/V bytes of full attention.

All kernel math is f32 (MXU accumulates f32 even for bf16 inputs via
preferred_element_type); outputs are cast back to the input dtype.

The public entry keeps the paddle layout [batch, seq, heads, head_dim]
(reference python/paddle API convention) and composes with the eager tape via
jax.custom_vjp. On CPU (tests) the kernel runs in Pallas interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tensor._helper import apply

_BLOCK_Q = 1024        # default tile edges (capped by seq len). Large tiles
_BLOCK_K = 1024        # amortize grid/DMA overhead and, at 1024, collapse
                       # seq<=1024 to ONE tile per (batch,head) — no
                       # running-softmax rescale passes (measured +5% MFU
                       # on GPT-350M vs 512 tiles; logits tile is 4 MiB
                       # f32, comfortably in VMEM). Equal q/k tiles under
                       # causal so the diagonal block covers its own row.
_SEQ_ALIGN = 128
_NEG_INF = -1e30

# The kernel's matmul semantics are part of the kernel, not of global
# config: under jax_default_matmul_precision="highest" (the test suite's
# golden-value setting) an unpinned dot_general would ask Mosaic for
# fp32-precision bf16 matmuls, which the bundled libtpu rejects ("Bad lhs
# type") — and 6-pass emulation is never what a flash kernel wants anyway.
_dot = functools.partial(jax.lax.dot_general,
                         precision=jax.lax.Precision.DEFAULT)
_LOG2E = 1.4426950408889634   # softmax runs in base 2: exp(x) = exp2(x·log2e)
_LN2 = 0.6931471805599453     # (exp2 is the TPU-native transcendental)


def _interpret() -> bool:
    from ..core.place import target_platform

    return target_platform() == "cpu"


def _causal_mask(iq, ik, block_q, block_k):
    qi = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    ki = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return qi >= ki


def _tile_class(iq, ik, block_q, block_k):
    """(live, crosses_diagonal) for causal tile (iq, ik)."""
    q_lo, q_hi = iq * block_q, iq * block_q + block_q - 1
    k_lo, k_hi = ik * block_k, ik * block_k + block_k - 1
    live = k_lo <= q_hi
    diag = live & (k_hi > q_lo)
    return live, diag


def _pick_block(seq, cap):
    """Largest block edge <= cap that divides seq (128-aligned), else None."""
    b = min(cap, seq)
    while b >= _SEQ_ALIGN:
        if seq % b == 0:
            return b
        b //= 2
    return None


def supported(q_shape, attn_mask, dropout_p, kv_seq=None,
              kv_heads=None) -> bool:
    """True when the Pallas kernel handles this case; else jnp path.
    ``kv_heads``: the key/value heads where they are fewer than the
    query heads (grouped-query attention); they must divide them."""
    if attn_mask is not None or dropout_p:
        return False
    if len(q_shape) != 4:
        return False
    if kv_heads is not None and (kv_heads < 1 or q_shape[2] % kv_heads):
        return False
    if _pick_block(q_shape[1], _BLOCK_Q) is None:
        return False
    if kv_seq is not None and _pick_block(kv_seq, _BLOCK_K) is None:
        return False
    return q_shape[3] <= 256


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale, causal, block_q, block_k):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _tile(masked):
        q = q_ref[0]                                     # [bq, d]
        k = k_ref[0]                                     # [bk, d]
        v = v_ref[0]
        # base-2 logits: one fused scale, exp2 on the VPU (cheaper than exp)
        s = _dot(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (scale * _LOG2E)
        if masked:
            mask = _causal_mask(iq, ik, block_q, block_k)
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:]                                # [bq, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_cur)
        p = jnp.exp2(s - m_cur)
        if masked:
            p = jnp.where(mask, p, 0.0)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + _dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_cur

    if causal:
        # tiles fully below the diagonal skip masking; tiles crossing it mask;
        # tiles fully above are dead (no compute, MXU never sees them)
        live, diag = _tile_class(iq, ik, block_q, block_k)
        pl.when(live & ~diag)(lambda: _tile(False))
        pl.when(diag)(lambda: _tile(True))
    else:
        _tile(False)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        # m is base-2; export the natural-log lse (bwd/ring contract)
        lse_ref[0] = (m_ref[:] + jnp.log2(l_safe)) * _LN2   # [bq, 1]


def _kv_index_maps(causal, group):
    """Index maps of the K/V blocks on a grid (query head, q block, k
    block): query head ``b`` reads key/value head ``b // group`` (heads
    are flattened batch-major, so the quotient is right across batches)."""
    if causal:
        # dead tiles (j past the diagonal) re-reference the diagonal block;
        # an unchanged block index between grid steps elides the DMA
        if group == 1:
            return lambda b, i, j: (b, jax.lax.min(j, i), 0)
        return lambda b, i, j: (b // group, jax.lax.min(j, i), 0)
    if group == 1:
        return lambda b, i, j: (b, j, 0)
    return lambda b, i, j: (b // group, j, 0)


def _fwd(q3, k3, v3, scale, causal, block_q, block_k):
    """q3: [BH, S, D], k3/v3: [BH / group, S, D] -> (o [BH, S, D], lse
    [BH, S, 1] f32)."""
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    nq, nk = sq // block_q, sk // block_k
    grid = (bh, nq, nk)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k)
    kv_idx = _kv_index_maps(causal, bh // k3.shape[0])
    o, lse = pl.pallas_call(
        kern,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),       # running max
            pltpu.VMEM((block_q, 1), jnp.float32),       # running denom
            pltpu.VMEM((block_q, d), jnp.float32),       # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * sq * sk * d // (2 if causal else 1),
            bytes_accessed=2 * (q3.size + k3.size + v3.size) * q3.dtype.itemsize,
            transcendentals=bh * sq * sk),
        interpret=_interpret(),
    )(q3, k3, v3)
    return o, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, block_q, block_k):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _tile(masked):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                                  # [bq, 1] natural
        delta = delta_ref[0]                              # [bq, 1]
        s = _dot(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (scale * _LOG2E)
        if masked:
            s = jnp.where(_causal_mask(iq, ik, block_q, block_k), s,
                          _NEG_INF)
        p = jnp.exp2(s - lse * _LOG2E)                    # [bq, bk]
        dp = _dot(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[:] += _dot(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        live, diag = _tile_class(iq, ik, block_q, block_k)
        pl.when(live & ~diag)(lambda: _tile(False))
        pl.when(diag)(lambda: _tile(True))
    else:
        _tile(False)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, block_q, block_k, group=1):
    # the innermost axis walks the q blocks of every query head of the
    # group in turn: dK and dV sum over the group in the accumulators
    ik, step = pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(2)
    iq = step if group == 1 else step % (last // group)

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _tile(masked):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                                  # [bq, 1] natural
        delta = delta_ref[0]                              # [bq, 1]
        s = _dot(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (scale * _LOG2E)
        if masked:
            s = jnp.where(_causal_mask(iq, ik, block_q, block_k), s,
                          _NEG_INF)
        p = jnp.exp2(s - lse * _LOG2E)                    # [bq, bk]
        dv_acc[:] += _dot(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # p^T @ do
        dp = _dot(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                     # [bq, bk]
        dk_acc[:] += _dot(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # ds^T @ q

    if causal:
        live, diag = _tile_class(iq, ik, block_q, block_k)
        pl.when(live & ~diag)(lambda: _tile(False))
        pl.when(diag)(lambda: _tile(True))
    else:
        _tile(False)

    @pl.when(step == last - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_single_tile_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                            delta_ref, dq_ref, dk_ref, dv_ref,
                            *, scale, causal):
    """Merged backward for the single-tile regime (whole sequence fits
    one q×k tile — the default at seq<=1024): s and p = exp2(s−lse) are
    computed ONCE and reused for dq, dk, and dv, where the two-kernel
    path recomputes them per kernel. Saves a full logits recompute per
    layer per step."""
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]                                      # [bq, 1] natural
    delta = delta_ref[0]                                  # [bq, 1]
    s = _dot(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (scale * _LOG2E)
    if causal:
        s = jnp.where(_causal_mask(0, 0, q.shape[0], k.shape[0]), s,
                      _NEG_INF)
    p = jnp.exp2(s - lse * _LOG2E)                        # [bq, bk]
    dv_ref[0] = _dot(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = _dot(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dsq = ds.astype(q.dtype)
    dq_ref[0] = _dot(
        dsq, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_ref[0] = _dot(
        dsq, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _bwd_single_tile(scale, causal, res, do3, delta, dtypes):
    q3, k3, v3, lse = res
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    dq_dtype, dk_dtype, dv_dtype = dtypes
    kern = functools.partial(_bwd_single_tile_kernel, scale=scale,
                             causal=causal)
    dq, dk, dv = pl.pallas_call(
        kern,
        name="flash_bwd",
        grid=(bh,),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sq, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sq, 1), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), dq_dtype),
            jax.ShapeDtypeStruct((bh, sk, d), dk_dtype),
            jax.ShapeDtypeStruct((bh, sk, d), dv_dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


def _bwd(scale, causal, block_q, block_k, res, do3, delta=None,
         out_dtype=None):
    """delta/out_dtype are overridable for the ring-attention caller
    (ops/ring_attention.py): there delta is a property of the GLOBAL
    output row (computed once outside the ring) and per-chunk partials
    must come back f32 so the ring accumulation doesn't round."""
    q3, k3, v3, o3, lse = res
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    nq, nk = sq // block_q, sk // block_k
    if delta is None:
        delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                        axis=-1, keepdims=True)           # [BH, S, 1]
    dq_dtype = out_dtype or q3.dtype
    dk_dtype = out_dtype or k3.dtype
    dv_dtype = out_dtype or v3.dtype

    group = bh // k3.shape[0]
    if nq == 1 and nk == 1 and group == 1:
        return _bwd_single_tile(scale, causal, (q3, k3, v3, lse), do3,
                                delta, (dq_dtype, dk_dtype, dv_dtype))

    # same dead-tile DMA elision as the forward (see module docstring)
    kv_idx = _kv_index_maps(causal, group)
    if group > 1:
        # grid (key/value head, k block, group x q blocks)
        head = lambda b, i: b * group + i // nq
        if causal:
            q_row_idx = lambda b, j, i: (head(b, i),
                                         jax.lax.max(i % nq, j), 0)
        else:
            q_row_idx = lambda b, j, i: (head(b, i), i % nq, 0)
    elif causal:
        q_row_idx = lambda b, j, i: (b, jax.lax.max(i, j), 0)
    else:
        q_row_idx = lambda b, j, i: (b, i, 0)

    dq_kern = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                block_q=block_q, block_k=block_k)
    dq = pl.pallas_call(
        dq_kern,
        name="flash_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=[pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), dq_dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q3, k3, v3, do3, lse, delta)[0]

    dkv_kern = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, **({"group": group} if group > 1 else {}))
    dk, dv = pl.pallas_call(
        dkv_kern,
        name="flash_bwd_dkv",
        grid=(bh // group, nk, group * nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_row_idx),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), q_row_idx),
            pl.BlockSpec((1, block_q, 1), q_row_idx),
            pl.BlockSpec((1, block_q, 1), q_row_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k3.shape, dk_dtype),
            jax.ShapeDtypeStruct(v3.shape, dv_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper (pure jax level, [B, S, H, D] layout)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_mha(q, k, v, causal, scale):
    o, _ = _flash_fwd_res(q, k, v, causal, scale)
    return o


def _reshape_in(x):
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)


def _reshape_out(x3, b, h):
    bh, s, d = x3.shape
    return jnp.swapaxes(x3.reshape(b, h, s, d), 1, 2)


def _flash_fwd_res(q, k, v, causal, scale):
    b, sq, h, d = q.shape
    s_val = scale if scale is not None else 1.0 / (d ** 0.5)
    sk = k.shape[1]
    bq = _pick_block(sq, _BLOCK_Q)
    bk = _pick_block(sk, _BLOCK_K)
    if bq is None or bk is None:
        raise ValueError(
            f"flash_attention needs 128-aligned seq lens, got q={sq} kv={sk}")
    if causal:
        if sq != sk:
            raise ValueError("causal flash_attention requires seq_q == seq_kv")
        bq = bk = min(bq, bk)
    q3, k3, v3 = _reshape_in(q), _reshape_in(k), _reshape_in(v)
    o3, lse = _fwd(q3, k3, v3, s_val, causal, bq, bk)
    return _reshape_out(o3, b, h), (q3, k3, v3, o3, lse, b, h, s_val, bq, bk)


def _flash_mha_bwd(causal, scale, res, do):
    q3, k3, v3, o3, lse, b, h, s_val, bq, bk = res
    do3 = _reshape_in(do)
    dq3, dk3, dv3 = _bwd(s_val, causal, bq, bk, (q3, k3, v3, o3, lse), do3)
    h_kv = k3.shape[0] // b
    return (_reshape_out(dq3, b, h), _reshape_out(dk3, b, h_kv),
            _reshape_out(dv3, b, h_kv))


_flash_mha.defvjp(_flash_fwd_res, _flash_mha_bwd)


def _nested_shard(q_shape, causal, scale):
    """Where some mesh axis is still GSPMD-auto (distributed/context.py
    ``auto_axes_scope``) XLA refuses the Mosaic call, so the kernel is
    wrapped in a shard_map over those axes: dp shards batch, tp shards
    heads (the framework's axis convention), any other axis replicates.
    Returns the wrapped callable, or None when the kernel can be called
    as it is. A batch or head count the mesh does not divide is an
    error: there is no substitute that is still the flash kernel."""
    from ..distributed import context as dctx

    pa = dctx.kernel_auto_axes()
    if pa is None:
        return None
    mesh, axes = pa
    from jax.sharding import PartitionSpec as P

    b, s, h, d = q_shape
    dp = mesh.shape.get("dp", 1) if "dp" in axes else 1
    tp = mesh.shape.get("tp", 1) if "tp" in axes else 1
    if b % dp or h % tp:
        raise ValueError(
            f"flash_attention on mesh {dict(mesh.shape)}: q shape "
            f"{tuple(q_shape)} needs batch {b} divisible by dp={dp} and "
            f"heads {h} divisible by tp={tp} (the Pallas kernel is "
            "sharded batch-over-dp, heads-over-tp; XLA cannot partition "
            "it automatically)")
    spec = P("dp" if dp > 1 else None, None, "tp" if tp > 1 else None,
             None)
    return dctx.nested_kernel_shard(
        lambda q_, k_, v_: _flash_mha(q_, k_, v_, causal, scale),
        in_specs=(spec, spec, spec), out_specs=spec)


def flash_attention(query, key, value, causal=False, scale=None, name=None):
    """q,k,v: [batch, seq, heads, head_dim] -> [batch, seq, heads, head_dim].

    Tape-level entry (Tensor in/out). ``_flash_mha`` is the pure-jax kernel
    entry used by jitted functional paths (distributed/hybrid_gpt.py).
    """
    return apply(lambda q, k, v: flash_mha(q, k, v, causal, scale),
                 query, key, value, name="flash_attention")


def flash_mha(q, k, v, causal=False, scale=None):
    """``flash_attention`` on arrays: the kernel, inside a shard_map where
    a multi-device auto mesh is open. k and v may have fewer heads than q,
    a divisor of them: query head ``i`` reads key/value head ``i // group``."""
    nested = _nested_shard(q.shape, causal, scale)
    if nested is not None:
        return nested(q, k, v)
    return _flash_mha(q, k, v, causal, scale)


def mha_reference(q, k, v, causal=False, scale=None):
    """Unfused reference (tests compare the kernel against this). Fewer
    key/value heads than query heads: each is repeated over its group."""
    d = q.shape[-1]
    if k.shape[2] != q.shape[2]:
        group = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    logits = (jnp.einsum("bhsd,bhtd->bhst", qt, kt) * s).astype(jnp.float32)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        logits = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


# back-compat alias (pre-kernel rounds exposed the reference as the impl)
_mha_reference = mha_reference
