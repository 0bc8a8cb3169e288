"""Quantized collectives: EQuARX-style compressed AllReduce for the
DP gradient path (ROADMAP item 3b; "EQuARX: Efficient Quantized
AllReduce in XLA", PAPERS.md).

A data-parallel step moves every gradient byte across the mesh once
per step, and on multi-host meshes that AllReduce IS the comm phase
the profiler accounts (``comm/collective_bytes_per_step``). EQuARX's
observation: the reduction tolerates low-precision *transport* as long
as *accumulation* stays high-precision — so quantize each hop of the
ring, not the math:

1. **Blockwise int8 quantization.** The flat gradient is cut into
   ``block``-element blocks; each block ships as int8 with one f32
   scale (``amax / 127``). Per-block scaling is what makes one outlier
   cost one block's precision instead of the whole tensor's (the same
   reasoning as the per-page per-head KV scales in
   ``serving/paged_cache.py`` and the per-tensor amax idiom of
   ``ops/int8_matmul.py``).
2. **Reduce-scatter in low precision, accumulate in f32.** A classic
   ring reduce-scatter (``N - 1`` ``ppermute`` hops) where every hop's
   payload is the quantized partial sum + its block scales; the
   receiver dequantizes, adds its own f32 shard, and re-quantizes for
   the next hop. Wire bytes per hop: ``T/N`` int8 + ``T/(N·block)``
   f32 scales, vs ``4·T/N`` for the f32 ring.
3. **Quantized all-gather.** Each device quantizes its fully-reduced
   shard once and all-gathers int8 + scales; everyone dequantizes
   locally.

Counted result-buffer bytes (what ``profiler.collective_stats``
measures): ``(N-1)/N·T + T`` int8 + scale overhead ≈ ``2T`` bytes vs
the f32 AllReduce's ``4T`` — ≤ 0.5x before scale overhead, ≤ 0.55x
with it at any ``block >= 64`` (the ISSUE 12 acceptance bound; the
per-dtype gauges ``comm/collective_bytes_{int8,f32}`` make the split
readable straight off the registry). Error per element is bounded by
one quantization step per hop plus one for the gather —
``<= (N) · amax_block / 254`` worst case, and in practice far below
it because partial sums concentrate (tests/test_qcomm.py pins the
bound and the loss-curve parity).

Integration: ``dp_grad_comm="int8"`` on ``HybridParallelTrainer``
(strategy_compiler.py) and ``HybridPipelineTrainer`` (hybrid.py).
Because GSPMD keeps the DP AllReduce *implicit* (mean loss over a
dp-sharded batch), the quantized path needs the pre-reduction
gradients — the trainers wrap the loss/grad computation in an
all-manual ``shard_map`` over the mesh, compute per-shard local
gradients, and reduce them through ``quantized_all_reduce_tree``
(one fused ring over the concatenated gradient buffer, the EQuARX
fused-buffer layout). Supported for pure data parallelism
(every non-dp mesh axis must be size 1) — composing with tp/pp is
ROADMAP residue.

All ops are plain jax collectives (``ppermute`` / ``all_gather``), so
the XLA graph is what runs on TPU — no host round-trip, and the
profiler's HLO byte accounting sees the real int8 payloads.

ZeRO composition (ISSUE 19; Xu et al., "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training", 2004.13336):
the ring's reduce-scatter half IS ZeRO's gradient sharding, so the
AllReduce is split into standalone :func:`quantized_reduce_scatter`
(shard r ends owning the fully-reduced flat chunk r) +
:func:`quantized_all_gather`, each with an f32 spelling
(``reduce_scatter`` / ``all_gather_cast``). :func:`dp_zero_step` is
the ONE shard_map wrap both trainers use for the sharded weight
update: reduce-scatter grads → clip/guard on the REDUCED shard →
shard-local elementwise optimizer update (state at chunk shape — the
memory win) → all-gather the updated params (``dp_param_comm`` picks
the f32/bf16/int8 return payload).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["quantize_blockwise", "dequantize_blockwise",
           "quantized_all_reduce", "quantized_all_reduce_tree",
           "quantized_reduce_scatter", "quantized_all_gather",
           "reduce_scatter", "all_gather_cast", "zero_chunk_len",
           "dp_zero_step", "validate_dp_grad_comm",
           "validate_dp_param_comm", "dp_batch_specs"]


def validate_dp_grad_comm(dp_grad_comm: str, mesh, *, zero_stage: int = 0,
                          block: int = 2048, unsupported=()) -> None:
    """The ONE validation of the trainers' ``dp_grad_comm`` knob
    (strategy_compiler.HybridParallelTrainer and
    hybrid.HybridPipelineTrainer share it so the constraints cannot
    drift): value in {'f32', 'int8'}; 'int8' additionally requires a
    positive block size, a pure-DP mesh (every non-dp axis size 1),
    ZeRO stage <= 2 (stages 1-2 ride the ring's reduce-scatter half;
    stage 3 is residue), and none of the caller's ``unsupported``
    (name, flag) feature pairs."""
    if dp_grad_comm not in ("f32", "int8"):
        raise ValueError(
            f"unknown dp_grad_comm {dp_grad_comm!r}; expected "
            "'f32' or 'int8'")
    if dp_grad_comm != "int8":
        return
    if block < 1:
        raise ValueError("dp_grad_block must be >= 1")
    other = {a: s for a, s in mesh.shape.items()
             if a != "dp" and s > 1}
    if other:
        raise NotImplementedError(
            f"dp_grad_comm='int8' supports pure data parallelism; "
            f"mesh has non-dp axes {other} (quantized collectives "
            "under tp/pp/sp are ROADMAP residue)")
    if zero_stage >= 3:
        raise NotImplementedError(
            "dp_grad_comm='int8' with ZeRO stage 3 (parameter "
            "sharding) is ROADMAP residue; stages 1-2 run the "
            "sharded weight update on the quantized ring")
    for name, flag in unsupported:
        if flag:
            raise NotImplementedError(
                f"dp_grad_comm='int8' does not compose with {name}")


def validate_dp_param_comm(dp_param_comm: str, zero_manual: bool) -> None:
    """Validation of the trainers' ``dp_param_comm`` knob (the
    all-gather payload of the ZeRO return half): value in
    {'f32', 'bf16', 'int8'}; the compressed spellings only mean
    anything on the manual sharded-update path."""
    if dp_param_comm not in ("f32", "bf16", "int8"):
        raise ValueError(
            f"unknown dp_param_comm {dp_param_comm!r}; expected "
            "'f32', 'bf16' or 'int8'")
    if dp_param_comm != "f32" and not zero_manual:
        raise ValueError(
            f"dp_param_comm={dp_param_comm!r} requires the manual "
            "ZeRO sharded update (zero_stage 1/2 on a pure-DP mesh "
            "with dp > 1); without it params never ride a collective")


def dp_quantized_value_and_grads(mesh, axis_size: int, block: int,
                                 fn, rep_args, batch, batch_specs,
                                 key):
    """THE quantized-DP shard_map wrap, shared by both trainers (like
    ``validate_dp_grad_comm``, so the semantics cannot drift):
    ``fn(rep_args, key, batch) -> (loss, aux, grads)`` runs once per
    dp shard inside an all-manual shard_map — replicated ``rep_args``,
    per-leaf-sharded ``batch``, the rng key folded with the shard
    index so dropout masks stay independent — and the reductions are
    pmean for the loss and floating ``aux`` leaves (non-float aux
    passes through: identical across shards by construction) and the
    quantized ring (mean) for ``grads``. Returns the reduced
    (loss, aux, grads)."""
    from jax.sharding import PartitionSpec as P

    def body(rep, key_, *batch_):
        key_ = jax.random.fold_in(key_, jax.lax.axis_index("dp"))
        loss, aux, grads = fn(rep, key_, batch_)
        loss = jax.lax.pmean(loss, "dp")
        aux = jax.tree_util.tree_map(
            lambda a: jax.lax.pmean(a, "dp")
            if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
            else a, aux)
        grads = quantized_all_reduce_tree(grads, "dp", axis_size,
                                          block=block, mean=True)
        return loss, aux, grads

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), P()) + tuple(batch_specs),
                         out_specs=(P(), P(), P()),
                         check_vma=False)(rep_args, key, *batch)


def dp_batch_specs(batch, dp: int):
    """Per-leaf shard_map in_specs for a batch tuple under the
    quantized-DP wrap (the no-``data_spec`` default). Under GSPMD,
    sharding any leaf's dim 0 is layout-only; under the MANUAL wrap a
    split is semantic — each shard computes on its slice — so only
    leaves that actually ride the batch axis may be split: dim 0 must
    equal the FIRST array leaf's dim 0 (the batch size — labels/aux
    inputs ride dim-0-aligned with the first, the ``tokens_in_batch``
    convention) and divide ``dp``. Everything else (masks, position
    vectors, scalars) replicates; an indivisible batch replicates
    everything, which degrades to every shard computing the full batch
    — wasteful but exact."""
    from jax.sharding import PartitionSpec as P

    lead = next((b.shape[0] for b in batch
                 if getattr(b, "ndim", 0) >= 1), None)
    if lead is None or lead % dp:
        return tuple(P() for _ in batch)
    return tuple(
        P("dp") if getattr(b, "ndim", 0) >= 1 and b.shape[0] == lead
        else P()
        for b in batch)

#: symmetric int8 range used for every payload (round-to-nearest-even
#: via jnp.round, the repo's int8_matmul convention)
_QMAX = 127.0


def quantize_blockwise(x: jax.Array, block: int = 2048
                       ) -> Tuple[jax.Array, jax.Array]:
    """Flat f32 vector (length divisible by ``block``) -> (int8 values,
    f32 per-block scales ``amax/127``). An all-zero block gets scale 0
    and quantizes to exact zeros (the null-block analogue of the KV
    pool's null-page scale)."""
    xb = x.reshape(-1, block)
    scale = jnp.max(jnp.abs(xb), axis=1) / _QMAX
    q = jnp.round(xb / jnp.maximum(scale, 1e-30)[:, None])
    q = jnp.clip(q, -_QMAX, _QMAX).astype(jnp.int8)
    return q.reshape(-1), scale


def dequantize_blockwise(q: jax.Array, scale: jax.Array,
                         block: int = 2048) -> jax.Array:
    """Inverse of :func:`quantize_blockwise` (f32 out)."""
    return (q.reshape(-1, block).astype(jnp.float32)
            * scale[:, None]).reshape(-1)


def _chunk(chunks: jax.Array, idx) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(chunks, idx, axis=0,
                                        keepdims=False)


def zero_chunk_len(total: int, axis_size: int, block: int) -> int:
    """Per-shard flat chunk length of the ZeRO/ring layout: ``total``
    elements split into one chunk per shard, each a whole number of
    quantization blocks. Callers pad their flat buffer to
    ``axis_size * zero_chunk_len(...)``."""
    return block * max(1, math.ceil(total / (axis_size * block)))


def quantized_reduce_scatter(x: jax.Array, axis_name: str,
                             axis_size: int, *, block: int = 2048,
                             mean: bool = False) -> jax.Array:
    """The quantized ring's reduce-scatter half, standalone (ZeRO's
    gradient sharding). ``x`` is the per-shard flat f32 buffer, padded
    to ``axis_size * chunk`` with ``chunk`` a multiple of ``block``
    (:func:`zero_chunk_len`); the return is the fully-reduced f32
    chunk THIS shard owns — shard ``r`` owns ``x[r*chunk:(r+1)*chunk]``
    — after ``axis_size - 1`` int8 ``ppermute`` hops with f32
    accumulation. Must run inside a shard_map manual over
    ``axis_name``."""
    n = int(axis_size)
    if n < 1:
        raise ValueError(f"axis_size must be >= 1, got {n}")
    if block < 1:
        raise ValueError(f"block must be >= 1, got {block}")
    flat = x.astype(jnp.float32).reshape(-1)
    if n == 1:
        return flat / n if mean else flat
    if flat.shape[0] % (n * block):
        raise ValueError(
            f"reduce-scatter input size {flat.shape[0]} must be a "
            f"multiple of axis_size*block = {n * block}; pad to "
            "zero_chunk_len first")
    chunks = flat.reshape(n, -1)
    r = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Ring reduce-scatter, int8 hops / f32 accumulation. Start one
    # chunk BEHIND the owned index so that after n-1 forward hops the
    # partial lands home: device r seeds chunk r-1, and at hop s adds
    # its own contribution to the incoming partial of chunk r-2-s;
    # after the last hop (s = n-2) it holds the full sum of chunk r.
    acc = _chunk(chunks, jnp.mod(r - 1, n))
    for s in range(n - 1):
        q, sc = quantize_blockwise(acc, block)
        q = jax.lax.ppermute(q, axis_name, perm)
        sc = jax.lax.ppermute(sc, axis_name, perm)
        acc = dequantize_blockwise(q, sc, block) \
            + _chunk(chunks, jnp.mod(r - 2 - s, n))
    return acc / n if mean else acc


def reduce_scatter(x: jax.Array, axis_name: str, axis_size: int, *,
                   mean: bool = False) -> jax.Array:
    """f32 spelling of :func:`quantized_reduce_scatter`: the same ring
    (same ownership — shard r gets chunk r — and the same pairwise f32
    accumulation order) with uncompressed hops. Input must be padded
    to a multiple of ``axis_size``."""
    n = int(axis_size)
    if n < 1:
        raise ValueError(f"axis_size must be >= 1, got {n}")
    flat = x.astype(jnp.float32).reshape(-1)
    if n == 1:
        return flat / n if mean else flat
    if flat.shape[0] % n:
        raise ValueError(
            f"reduce-scatter input size {flat.shape[0]} must be a "
            f"multiple of axis_size {n}")
    chunks = flat.reshape(n, -1)
    r = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc = _chunk(chunks, jnp.mod(r - 1, n))
    for s in range(n - 1):
        acc = jax.lax.ppermute(acc, axis_name, perm) \
            + _chunk(chunks, jnp.mod(r - 2 - s, n))
    return acc / n if mean else acc


def quantized_all_gather(chunk: jax.Array, axis_name: str, *,
                         block: int = 2048) -> jax.Array:
    """The quantized ring's all-gather half, standalone (ZeRO's param
    return): each shard quantizes its owned chunk once, all-gathers
    int8 + scales, and dequantizes locally. Because shard r owns chunk
    r, gathered row order IS chunk order — the flat f32 concatenation
    comes back directly."""
    q, sc = quantize_blockwise(chunk.astype(jnp.float32), block)
    qg = jax.lax.all_gather(q, axis_name, axis=0)
    sg = jax.lax.all_gather(sc, axis_name, axis=0)
    return (qg.reshape(qg.shape[0], -1, block).astype(jnp.float32)
            * sg[:, :, None]).reshape(-1)


def all_gather_cast(chunk: jax.Array, axis_name: str,
                    dtype=jnp.float32) -> jax.Array:
    """Uncompressed spelling of :func:`quantized_all_gather`: gather
    the owned chunk cast to ``dtype`` for transport (``bf16`` halves
    the payload at ~3 significand decimal digits; ``f32`` is exact)
    and return the flat f32 concatenation."""
    g = jax.lax.all_gather(chunk.astype(dtype), axis_name, axis=0)
    return g.astype(jnp.float32).reshape(-1)


def quantized_all_reduce(x: jax.Array, axis_name: str, axis_size: int,
                         *, block: int = 2048,
                         mean: bool = False) -> jax.Array:
    """EQuARX-style compressed AllReduce of ``x`` over ``axis_name``.

    Must run inside a ``shard_map`` region manual over ``axis_name``
    (``axis_size`` is the static axis size — the ring unrolls
    ``axis_size - 1`` hops at trace time). Transport is blockwise int8
    with f32 block scales; accumulation is f32; the result is
    replicated across the axis. ``mean=True`` divides by the axis size
    (the DP-gradient convention). Output keeps ``x``'s shape/dtype.

    Spelled as the composition of the standalone ring halves:
    :func:`quantized_reduce_scatter` then :func:`quantized_all_gather`
    (the ZeRO split of ISSUE 19 — an AllReduce is exactly the two
    halves back to back with no compute between).
    """
    n = int(axis_size)
    if n < 1:
        raise ValueError(f"axis_size must be >= 1, got {n}")
    if block < 1:
        raise ValueError(f"block must be >= 1, got {block}")
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    if n == 1:
        return (flat / n if mean else flat).reshape(orig_shape) \
            .astype(orig_dtype)
    size = flat.shape[0]
    chunk = zero_chunk_len(size, n, block)
    flat = jnp.pad(flat, (0, chunk * n - size))
    acc = quantized_reduce_scatter(flat, axis_name, n, block=block,
                                   mean=mean)
    full = quantized_all_gather(acc, axis_name, block=block)[:size]
    return full.reshape(orig_shape).astype(orig_dtype)


def quantized_all_reduce_tree(tree, axis_name: str, axis_size: int,
                              *, block: int = 2048, mean: bool = False):
    """:func:`quantized_all_reduce` over a whole gradient pytree as ONE
    fused ring (EQuARX's fused-buffer layout: one concatenated flat
    buffer -> one reduce-scatter + one all-gather instead of a
    collective per leaf). Leaves are cast to f32 for transport and
    restored to their own shapes/dtypes."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    flat = jnp.concatenate(
        [jnp.asarray(l).astype(jnp.float32).reshape(-1) for l in leaves])
    red = quantized_all_reduce(flat, axis_name, axis_size, block=block,
                               mean=mean)
    out, off = [], 0
    for l in leaves:
        sz = int(jnp.size(l))
        out.append(red[off:off + sz].reshape(jnp.shape(l))
                   .astype(jnp.asarray(l).dtype))
        off += sz
    return jax.tree_util.tree_unflatten(treedef, out)


def dp_zero_step(mesh, axis_size: int, block: int, grad_comm: str,
                 param_comm: str, fn, update_fn, rep_args, params,
                 flat_state, batch, batch_specs, key, lr, step_no,
                 plr, wd, *, clip_norm=None, guard: bool = False):
    """THE ZeRO sharded-weight-update shard_map wrap, shared by both
    trainers (like ``dp_quantized_value_and_grads``, so the semantics
    cannot drift). One manual region over ``dp`` does the whole step:

    1. ``fn(rep_args, params, key, batch) -> (loss, aux, grads)`` runs
       per shard on its batch slice (key folded with the shard index);
       loss and floating ``aux`` leaves are pmean'd.
    2. Gradients are flattened into ONE fused f32 buffer (EQuARX
       layout), padded to ``axis_size * chunk``
       (:func:`zero_chunk_len`), and reduce-scattered (mean) to their
       owner shard — the quantized ring for ``grad_comm='int8'``, the
       f32 ring otherwise. Per-replica transient grad memory after
       this point is ``chunk``, not ``total``.
    3. Global-norm clipping (when ``clip_norm`` is set) via a psum of
       per-shard squared chunk sums — mathematically the full-tensor
       norm, computed without regathering.
    4. ``guard=True`` computes the bad-step verdict HERE, on the
       reduced shard grads + pmean'd loss, and pmin-agrees it across
       the mesh so every shard takes the identical keep/skip branch.
    5. ``update_fn(p_chunk, g_chunk, moments, lr, step_no, plr, wd)
       -> (new_p_chunk, new_moments)`` runs shard-locally on the owned
       flat slice. The parameter chunk comes from ``flat_state
       ['master']`` when present (the f32 master copy required for
       compressed ``param_comm`` — bf16 round-trip rounding would
       swallow small updates), else it is sliced out of the replicated
       params. Optimizer state lives at chunk shape: the memory win.
    6. A guarded-bad step deselects the NEW state bitwise (moments and
       master keep their previous values).
    7. The updated chunk all-gathers back — f32 exact, bf16 cast, or
       the quantized gather per ``param_comm`` — and leaves are
       restored to their shapes/dtypes; on a guarded-bad step every
       leaf reverts bitwise to its input value (the deselect happens
       AFTER the gather, so compressed-payload garbage from a NaN step
       is discarded, never applied).

    ``plr`` / ``wd`` are per-parameter learning-rate multipliers /
    weight-decay factors: scalars when uniform, else flat
    ``axis_size * chunk`` f32 vectors laid out exactly like the fused
    param buffer (they enter the shard_map with spec ``P('dp')`` and
    arrive pre-sliced to the owned chunk).

    Returns ``(loss, aux, new_params, new_flat_state)`` plus the
    mesh-agreed ``ok`` bool when ``guard``. ``new_flat_state`` keeps
    the dp-sharded layout (out_spec ``P('dp')``); everything else is
    replicated.
    """
    from jax.sharding import PartitionSpec as P

    n = int(axis_size)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    sizes = [int(jnp.size(l)) for l in leaves]
    total = sum(sizes)
    chunk = zero_chunk_len(total, n, block)
    pad = chunk * n - total

    def _knob_spec(v):
        return P("dp") if getattr(v, "ndim", 0) >= 1 else P()

    def body(rep, params_, state, key_, lr_, step_no_, plr_, wd_,
             *batch_):
        key_ = jax.random.fold_in(key_, jax.lax.axis_index("dp"))
        loss, aux, grads = fn(rep, params_, key_, batch_)
        loss = jax.lax.pmean(loss, "dp")
        aux = jax.tree_util.tree_map(
            lambda a: jax.lax.pmean(a, "dp")
            if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
            else a, aux)

        gleaves = jax.tree_util.tree_leaves(grads)
        flat_g = jnp.concatenate(
            [g.astype(jnp.float32).reshape(-1) for g in gleaves])
        flat_g = jnp.pad(flat_g, (0, pad))
        if grad_comm == "int8":
            g_c = quantized_reduce_scatter(flat_g, "dp", n, block=block,
                                           mean=True)
        else:
            g_c = reduce_scatter(flat_g, "dp", n, mean=True)

        if clip_norm is not None:
            gsq = jax.lax.psum(jnp.sum(jnp.square(g_c)), "dp")
            gn = jnp.sqrt(gsq)
            g_c = g_c * jnp.where(gn > clip_norm, clip_norm / gn, 1.0)

        ok = None
        if guard:
            ok_local = jnp.logical_and(
                jnp.isfinite(loss), jnp.all(jnp.isfinite(g_c)))
            ok = jax.lax.pmin(ok_local.astype(jnp.int32), "dp") \
                .astype(jnp.bool_)

        pleaves = jax.tree_util.tree_leaves(params_)
        if "master" in state:
            p_c = state["master"]
        else:
            flat_p = jnp.concatenate(
                [p0.astype(jnp.float32).reshape(-1) for p0 in pleaves])
            flat_p = jnp.pad(flat_p, (0, pad))
            r = jax.lax.axis_index("dp")
            p_c = jax.lax.dynamic_slice(flat_p, (r * chunk,), (chunk,))
        moments = {k: v for k, v in state.items() if k != "master"}
        new_p_c, new_moments = update_fn(p_c, g_c, moments, lr_,
                                         step_no_, plr_, wd_)
        new_state = dict(new_moments)
        if "master" in state:
            new_state["master"] = new_p_c
        if ok is not None:
            new_state = {k: jnp.where(ok, v, state[k])
                         for k, v in new_state.items()}

        if param_comm == "int8":
            full = quantized_all_gather(new_p_c, "dp", block=block)
        elif param_comm == "bf16":
            full = all_gather_cast(new_p_c, "dp", jnp.bfloat16)
        else:
            full = all_gather_cast(new_p_c, "dp", jnp.float32)
        out_leaves, off = [], 0
        for p0, sz in zip(pleaves, sizes):
            nl = full[off:off + sz].reshape(p0.shape).astype(p0.dtype)
            if ok is not None:
                nl = jnp.where(ok, nl, p0)
            out_leaves.append(nl)
            off += sz
        new_params = jax.tree_util.tree_unflatten(treedef, out_leaves)
        if guard:
            return loss, aux, new_params, new_state, ok
        return loss, aux, new_params, new_state

    in_specs = (P(), P(), P("dp"), P(), P(), P(),
                _knob_spec(plr), _knob_spec(wd)) + tuple(batch_specs)
    out_specs = (P(), P(), P(), P("dp"))
    if guard:
        out_specs = out_specs + (P(),)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(
        rep_args, params, flat_state, key, lr, step_no, plr, wd,
        *batch)
