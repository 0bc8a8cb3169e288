"""Ring attention — context/sequence parallelism over a mesh axis.

NEW capability vs the reference: the reference has no ring attention, no
context/sequence parallelism anywhere in the tree (SURVEY.md §5
"Long-context / sequence parallelism — Absent"). Long sequences there are
handled only by recompute + pipeline micro-batching. Here sequence
parallelism is first-class (a north-star requirement): activations are
sharded along the sequence dim over mesh axis 'sp', and attention runs as
a ring — each device holds its local Q chunk while K/V chunks rotate
around the ring via `lax.ppermute` (the XLA collective-permute that rides
ICI neighbor links), overlapping each hop with the blockwise-attention
compute of the previous chunk.

Design (blockwise/flash formulation, cf. PAPERS.md Ring Attention):
  - uniform chunking: all devices hold S/sp rows, so the causal structure
    is chunk-granular — a K/V chunk from source rank `src` vs local Q of
    rank `idx` is: fully visible (src < idx), the causal diagonal
    (src == idx), or fully masked (src > idx). No offset-aware kernel is
    needed: the diagonal chunk is exactly ordinary causal attention, so
    the existing Pallas flash kernels (ops/flash_attention.py) are reused
    per ring step; `lax.switch` picks the branch per step since `src`
    depends on the traced `axis_index`.
  - online-softmax merge across ring steps: each step returns the chunk's
    normalized output plus its logsumexp; steps combine with the standard
    (m, w, acc) running-max merge, so logits never materialize globally
    (O(S_local) memory per device).
  - backward is a second ring: dK/dV partial accumulators travel around
    the ring WITH their K/V chunk and arrive home after sp hops, while dQ
    accumulates locally. This replaces a gather of full K/V grads with
    neighbor permutes (the same trick the fwd uses).
  - fully-masked steps still pay the permute (the ring must stay in
    lockstep) but skip all compute. Rank 0 computes only its diagonal —
    the classic contiguous-sharding imbalance; a striped ("zigzag")
    layout is future work.

Layouts: public entry [batch, seq_local, heads, head_dim] (paddle
convention, matches flash_attention). `ring_attention` is the
inside-shard_map form; `sequence_parallel_attention` wraps it in a
partial-manual shard_map over just the sp axis so dp/tp stay in GSPMD
auto mode (composes with the tp-sharded head dim and dp-sharded batch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from . import flash_attention as fa

_NEG_INF = -1e30


def _use_pallas(sq, sk, d) -> bool:
    return (fa._pick_block(sq, fa._BLOCK_Q) is not None
            and fa._pick_block(sk, fa._BLOCK_K) is not None
            and d <= 256 and sq == sk)


# ---------------------------------------------------------------------------
# per-chunk forward: (o normalized, lse) both [BH, S, *]
# ---------------------------------------------------------------------------


def _chunk_fwd_jnp(q3, k3, v3, scale, causal):
    s = jnp.einsum("bqd,bkd->bqk", q3, k3,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bqk,bkd->bqd", (p / l).astype(v3.dtype), v3,
                   preferred_element_type=jnp.float32).astype(q3.dtype)
    return o, (m + jnp.log(l))


def _bh_kernel_shard(fn, n_in, n_out, bh):
    """Mosaic where some mesh axis is still GSPMD-auto: wrap a
    [BH, S, *]-chunk kernel call in a shard_map over those axes (shared
    rule: distributed/context.nested_kernel_shard). Row attention is
    independent per BH row, so ANY even partition of dim 0 is
    numerically exact — P((dp, tp)) contiguous chunks are used even
    though flattened b-major/h-minor order interleaves them. Returns
    None when the kernel can be called as it is; a BH the mesh does not
    divide is an error, not a reason to leave the kernel."""
    from ..distributed import context as dctx
    from jax.sharding import PartitionSpec as P

    pa = dctx.kernel_auto_axes()
    if pa is None:
        return None
    mesh, axes = pa
    dim0 = tuple(a for a in ("dp", "tp")
                 if a in axes and mesh.shape.get(a, 1) > 1)
    size = 1
    for a in dim0:
        size *= mesh.shape[a]
    if bh % size:
        raise ValueError(
            f"ring_attention on mesh {dict(mesh.shape)}: batch×heads "
            f"{bh} must be divisible by dp×tp={size} (the Pallas chunk "
            "kernels are sharded over those axes; XLA cannot partition "
            "them automatically)")
    spec = P(dim0 if dim0 else None, None, None)
    return dctx.nested_kernel_shard(fn, in_specs=(spec,) * n_in,
                                    out_specs=(spec,) * n_out)


def _chunk_fwd(q3, k3, v3, scale, causal):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    if _use_pallas(sq, sk, d):
        bq = fa._pick_block(sq, fa._BLOCK_Q)
        bk = fa._pick_block(sk, fa._BLOCK_K)
        if causal:
            bq = bk = min(bq, bk)
        nested = _bh_kernel_shard(
            lambda a, b, c: fa._fwd(a, b, c, scale, causal, bq, bk),
            n_in=3, n_out=2, bh=bh)
        if nested is not None:
            return nested(q3, k3, v3)
        return fa._fwd(q3, k3, v3, scale, causal, bq, bk)
    return _chunk_fwd_jnp(q3, k3, v3, scale, causal)


def _chunk_skip(q3, k3, v3, scale):
    bh, sq, d = q3.shape
    return (jnp.zeros((bh, sq, d), q3.dtype),
            jnp.full((bh, sq, 1), _NEG_INF, jnp.float32))


# ---------------------------------------------------------------------------
# per-chunk backward: (dq, dk, dv) given global (lse, delta)
# ---------------------------------------------------------------------------


def _chunk_bwd_jnp(q3, k3, v3, do3, lse, delta, scale, causal):
    s = jnp.einsum("bqd,bkd->bqk", q3, k3,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), s, _NEG_INF)
    p = jnp.exp(s - lse)                                   # [BH, sq, sk]
    dv = jnp.einsum("bqk,bqd->bkd", p.astype(do3.dtype), do3,
                    preferred_element_type=jnp.float32)
    dp = jnp.einsum("bqd,bkd->bqk", do3, v3,
                    preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bqk,bkd->bqd", ds.astype(k3.dtype), k3,
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bqk,bqd->bkd", ds.astype(q3.dtype), q3,
                    preferred_element_type=jnp.float32)
    return dq, dk, dv


def _chunk_bwd(q3, k3, v3, do3, lse, delta, scale, causal):
    """Returns f32 (dq, dk, dv) for one K/V chunk against local Q."""
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    if _use_pallas(sq, sk, d):
        bq = fa._pick_block(sq, fa._BLOCK_Q)
        bk = fa._pick_block(sk, fa._BLOCK_K)
        if causal:
            bq = bk = min(bq, bk)
        # o3 in res is only used for delta, which we precompute (it is a
        # property of the GLOBAL output row); out_dtype f32 so per-chunk
        # partials don't round before the ring accumulation.
        nested = _bh_kernel_shard(
            lambda q_, k_, v_, do_, lse_, delta_: fa._bwd(
                scale, causal, bq, bk, (q_, k_, v_, None, lse_), do_,
                delta=delta_, out_dtype=jnp.float32),
            n_in=6, n_out=3, bh=bh)
        if nested is not None:
            return nested(q3, k3, v3, do3, lse, delta)
        return fa._bwd(scale, causal, bq, bk, (q3, k3, v3, None, lse), do3,
                       delta=delta, out_dtype=jnp.float32)
    return _chunk_bwd_jnp(q3, k3, v3, do3, lse, delta, scale, causal)


# ---------------------------------------------------------------------------
# the ring (inside shard_map over `axis_name`)
# ---------------------------------------------------------------------------


def _ring_shift(xs, axis_name, n):
    perm = [(i, (i + 1) % n) for i in range(n)]
    return tuple(lax.ppermute(x, axis_name, perm) for x in xs)


def _branch(t, idx, sp, causal):
    """0 = skip (masked), 1 = full, 2 = diagonal-causal — for ring step t."""
    if not causal:
        return jnp.int32(1), None
    src = (idx - t) % sp
    return jnp.where(src > idx, 0, jnp.where(src < idx, 1, 2)), src


def _auto_scope(auto_ctx):
    """Re-enter the auto_axes scope captured at call time.
    custom_vjp backwards are traced at TRANSPOSE time, long after the
    caller's ``with`` scope exited — so the (mesh, axes) pair rides the
    nondiff args and both fwd and bwd re-enter it around their chunk
    kernels."""
    import contextlib

    from ..distributed import context as dctx

    if auto_ctx is None:
        return contextlib.nullcontext()
    return dctx.auto_axes_scope(auto_ctx[0], auto_ctx[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_mha(q, k, v, causal, scale, axis_name, auto_ctx=None):
    o, _ = _ring_fwd_res(q, k, v, causal, scale, axis_name, auto_ctx)
    return o


def _boundary_f32(dtype) -> bool:
    # XLA:CPU crashes on bf16 collectives inside (nested) manual regions
    # (same bug the pipeline works around, distributed/pipeline.py); TPU
    # keeps native bf16 ring transfers.
    from ..core.place import target_platform

    return target_platform() == "cpu" and dtype == jnp.bfloat16


def _ring_fwd_res(q, k, v, causal, scale, axis_name, auto_ctx=None):
    b, s_loc, h, d = q.shape
    sp = lax.psum(1, axis_name)     # axis size: static int under shard_map
    raise_if_not_static(sp)
    idx = lax.axis_index(axis_name)
    s_val = scale if scale is not None else 1.0 / (d ** 0.5)

    out_dtype = q.dtype
    if _boundary_f32(q.dtype):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    q3 = fa._reshape_in(q)
    k3 = fa._reshape_in(k)
    v3 = fa._reshape_in(v)
    bh = q3.shape[0]

    m = jnp.full((bh, s_loc, 1), _NEG_INF, jnp.float32)
    w = jnp.zeros((bh, s_loc, 1), jnp.float32)
    acc = jnp.zeros((bh, s_loc, d), jnp.float32)
    k_c, v_c = k3, v3
    with _auto_scope(auto_ctx):
        for t in range(sp):
            br, _ = _branch(t, idx, sp, causal)
            o_t, lse_t = lax.switch(
                br,
                [lambda q_, k_, v_: _chunk_skip(q_, k_, v_, s_val),
                 lambda q_, k_, v_: _chunk_fwd(q_, k_, v_, s_val, False),
                 lambda q_, k_, v_: _chunk_fwd(q_, k_, v_, s_val, True)],
                q3, k_c, v_c)
            m_new = jnp.maximum(m, lse_t)
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(lse_t - m_new)
            acc = acc * alpha + o_t.astype(jnp.float32) * beta
            w = w * alpha + beta
            m = m_new
            if t < sp - 1:
                k_c, v_c = _ring_shift((k_c, v_c), axis_name, sp)
    w_safe = jnp.where(w == 0.0, 1.0, w)
    o3 = (acc / w_safe).astype(q.dtype)
    lse = m + jnp.log(w_safe)
    o = fa._reshape_out(o3, b, h).astype(out_dtype)
    return o, (q3, k3, v3, o3, lse, b, h, s_val)


def _ring_bwd(causal, scale, axis_name, auto_ctx, res, do):
    q3, k3, v3, o3, lse, b, h, s_val = res
    sp = lax.psum(1, axis_name)
    raise_if_not_static(sp)
    idx = lax.axis_index(axis_name)
    out_dtype = do.dtype           # cotangent dtype == primal out dtype
    do3 = fa._reshape_in(do.astype(q3.dtype))
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)

    dq = jnp.zeros_like(q3, jnp.float32)
    dk_c = jnp.zeros_like(k3, jnp.float32)
    dv_c = jnp.zeros_like(v3, jnp.float32)
    k_c, v_c = k3, v3

    def _zero(q_, k_, v_, do_, lse_, delta_):
        return (jnp.zeros_like(q_, jnp.float32),
                jnp.zeros_like(k_, jnp.float32),
                jnp.zeros_like(v_, jnp.float32))

    with _auto_scope(auto_ctx):
        for t in range(sp):
            br, _ = _branch(t, idx, sp, causal)
            dq_t, dk_t, dv_t = lax.switch(
                br,
                [_zero,
                 lambda q_, k_, v_, do_, l_, dl_: _chunk_bwd(
                     q_, k_, v_, do_, l_, dl_, s_val, False),
                 lambda q_, k_, v_, do_, l_, dl_: _chunk_bwd(
                     q_, k_, v_, do_, l_, dl_, s_val, True)],
                q3, k_c, v_c, do3, lse, delta)
            dq = dq + dq_t
            dk_c = dk_c + dk_t
            dv_c = dv_c + dv_t
            # dK/dV accumulators travel WITH their chunk; after sp hops
            # they are home. K/V only need sp-1 hops (last compute used
            # the final position), so the last tick ships just the grads.
            if t < sp - 1:
                k_c, v_c, dk_c, dv_c = _ring_shift(
                    (k_c, v_c, dk_c, dv_c), axis_name, sp)
            else:
                dk_c, dv_c = _ring_shift((dk_c, dv_c), axis_name, sp)

    dq_ = fa._reshape_out(dq.astype(out_dtype), b, h)
    dk_ = fa._reshape_out(dk_c.astype(out_dtype), b, h)
    dv_ = fa._reshape_out(dv_c.astype(out_dtype), b, h)
    return dq_, dk_, dv_


_ring_mha.defvjp(_ring_fwd_res, _ring_bwd)


def raise_if_not_static(sp):
    if not isinstance(sp, int):
        raise TypeError(
            "ring_attention requires a static sp axis size (use it inside "
            "shard_map over a mesh axis)")


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = True,
                   scale=None):
    """Blockwise ring attention for use INSIDE shard_map.

    q, k, v: [batch, seq_local, heads, head_dim] — the local sequence
    shard. Returns the local shard of the attention output. Differentiable
    (custom VJP runs the backward ring).
    """
    from ..distributed import context as dctx

    return _ring_mha(q, k, v, causal, scale, axis_name,
                     dctx.current_auto_axes())


def sequence_parallel_attention(q, k, v, mesh: Mesh, causal: bool = True,
                                scale=None, axis_name: str = "sp"):
    """shard_map wrapper: q/k/v are GLOBAL [B, S, H, D] arrays (or traced
    values inside a pjit program); sequence dim is sharded over
    `axis_name`, everything else stays in GSPMD auto mode (so dp-sharded
    batch and tp-sharded heads compose)."""
    # when already inside another shard_map (e.g. the 'pp' pipeline,
    # distributed/pipeline.py), the context mesh is an AbstractMesh with
    # that axis Manual — the nested shard_map must be given THAT mesh.
    am = jax.sharding.get_abstract_mesh()
    use_mesh = am if axis_name in am.axis_names else mesh

    spec = P(None, axis_name, None, None)
    # inside this sp-manual region the other mesh axes stay GSPMD-auto;
    # pass them as the kernels' auto-context so the chunk kernels nest a
    # shard_map over them on the TPU target (XLA does not partition a
    # Mosaic call) — threaded through _ring_mha's static args so the
    # transpose-time backward sees it too
    remaining = tuple(a for a in mesh.axis_names if a != axis_name)
    auto_ctx = (mesh, remaining) if remaining else None

    mapped = jax.shard_map(
        lambda a, b_, c: _ring_mha(a, b_, c, causal, scale, axis_name,
                                   auto_ctx),
        mesh=use_mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False, axis_names=frozenset({axis_name}))
    return mapped(q, k, v)
