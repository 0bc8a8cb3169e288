"""Fused int8 matmul Pallas kernel: quantize → int8×int8→int32 MXU dot
→ dequant/bias/activation epilogue in ONE kernel.

Why (VERDICT r4 next #2): the unfused int8 serving path
(quantization.Int8Linear) lowers to XLA as three stages —

    f32 x ── round/clip ──▶ int8 xq  ──▶ MXU dot ──▶ int32 acc ──▶
    acc·scale + bias (f32 epilogue pass)

— and the int32 accumulator plus the quantize pass round-trip HBM.
At the serving bench's shapes ([4096, 4096]×[4096, 16384]) that is
~0.5 GB of avoidable traffic per layer, and the measured int8 dots ran
at ~43% of the v5e's int8 peak vs the bf16 artifact's ~61% (bench.py
predictor roofline note). This kernel keeps the quantize on the VPU
overlapped with the MXU dot, accumulates in VMEM, and applies the
dequant epilogue (per-channel scale, bias, optional ReLU, optional
re-quantize to int8 for a following int8 layer) before anything
touches HBM: per-layer HBM traffic becomes one read of x + one read
of wq + one write of the (possibly int8) output.

Reference analogue: the slim int8 deploy path hands quantized programs
to fused cuDNN/TensorRT int8 kernels inside AnalysisPredictor
(reference: python/paddle/fluid/contrib/slim/quantization/
quantization_pass.py, paddle/fluid/inference/api/analysis_predictor.cc);
this is the TPU-native equivalent of those fused kernels.

Math matches Int8Linear's unfused expression to f32 rounding (same
round-half-even, same clip bounds), so QAT-eval parity carries over.
On CPU (tests) the kernel runs in Pallas interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    from ..core.place import target_platform

    return target_platform() == "cpu"


def _kernel(x_ref, wq_ref, qs_ref, sc_ref, bi_ref, out_ref, acc_ref, *,
            nk: int, amax: float, relu: bool, quant_out: bool,
            x_quantized: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if x_quantized:
        xq = x_ref[:]
    else:
        # quantize on the VPU, overlapped with the MXU dot
        xq = jnp.clip(jnp.round(x_ref[:].astype(jnp.float32)
                                * qs_ref[0, 0]),
                      -amax, amax).astype(jnp.int8)
    acc_ref[:] += jax.lax.dot_general(
        xq, wq_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[:].astype(jnp.float32) * sc_ref[:] + bi_ref[:]
        if relu:
            y = jnp.maximum(y, 0.0)
        if quant_out:
            out_ref[:] = jnp.clip(jnp.round(y), -amax, amax) \
                .astype(jnp.int8)
        else:
            out_ref[:] = y.astype(out_ref.dtype)


def _pad_to(a, axis, mult):
    n = a.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(
    jax.jit, static_argnames=("relu", "quant_out", "out_dtype", "amax",
                              "block_m", "block_n", "block_k"))
def int8_matmul(x, wq, scale, bias=None, qscale=None, *,
                relu: bool = False, quant_out: bool = False,
                out_dtype=jnp.float32, amax: float = 127.0,
                block_m: int = 512, block_n: int = 512,
                block_k: int = 512):
    """y = dequant(quantize(x) @ wq) [+ bias] [relu] [requantize].

    x:      [M, K] float (quantized in-kernel with ``qscale``) or int8
            (pre-quantized; ``qscale`` ignored).
    wq:     [K, N] int8.
    scale:  [N] f32 — combined dequant scale applied to the int32
            accumulator (caller folds (s_act/amax)·(s_w/wmax) and, for
            ``quant_out``, the NEXT layer's amax/s_act into it).
    bias:   optional [N] f32, added post-scale (pre-ReLU). For
            ``quant_out`` the caller folds the next quant scale in.
    quant_out: emit int8 (clip(round(y))) for a following int8 layer —
            the f32 intermediate never exists in HBM.
    """
    m, kdim = x.shape
    n = wq.shape[1]
    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, kdim))
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(wq, 0, bk), 1, bn)
    sp = _pad_to(scale.reshape(1, -1).astype(jnp.float32), 1, bn)
    bp = _pad_to(
        (bias if bias is not None
         else jnp.zeros((n,), jnp.float32)).reshape(1, -1)
        .astype(jnp.float32), 1, bn)
    qs = jnp.asarray(qscale if qscale is not None else 1.0,
                     jnp.float32).reshape(1, 1)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    nk = kp // bk
    grid = (mp // bm, np_ // bn, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, amax=float(amax), relu=relu,
                          quant_out=quant_out,
                          x_quantized=(x.dtype == jnp.int8)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (mp, np_), jnp.int8 if quant_out else out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(xp, wp, qs, sp, bp)
    return out[:m, :n]


def int8_linear_fused(x, wq, w_scale, act_scale, bias=None, *,
                      wmax: float = 127.0, amax: float = 127.0,
                      relu: bool = False,
                      next_act_scale: Optional[jax.Array] = None,
                      out_dtype=jnp.float32):
    """Int8Linear's math through the fused kernel.

    Folds the per-channel dequant (and, when ``next_act_scale`` is
    given, the next layer's activation quantization) into the kernel
    epilogue:

        y   = (xq @ wq) · (s_a/amax)·(s_w/wmax) + b          (f32)
        yq  = clip(round(y · amax/s_a'))                      (int8)

    x may be f32/bf16 (quantized in-kernel) or int8 (output of a
    previous ``quant_out`` layer).
    """
    sa = jnp.maximum(jnp.asarray(act_scale, jnp.float32), 1e-8)
    ws = jnp.maximum(jnp.asarray(w_scale, jnp.float32), 1e-8)
    scale = (sa / amax) * (ws / wmax)
    b = None if bias is None else jnp.asarray(bias, jnp.float32)
    quant_out = next_act_scale is not None
    if quant_out:
        nq = amax / jnp.maximum(jnp.asarray(next_act_scale, jnp.float32),
                                1e-8)
        scale = scale * nq
        if b is not None:
            b = b * nq
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    y = int8_matmul(x2, wq, scale, b, qscale=amax / sa, relu=relu,
                    quant_out=quant_out, out_dtype=out_dtype, amax=amax)
    return y.reshape(lead + (wq.shape[1],))
