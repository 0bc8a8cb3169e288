"""Pipeline parallelism over a mesh axis.

TPU-native replacement for the reference pipeline stack
(reference: fleet meta_optimizers/pipeline_optimizer.py:136 splitting the
program by op_device + send_v2/recv_v2 ops; PipelineTrainer/SectionWorker
section_worker.cc:34 F-then-B thread-per-stage schedule).

This module is the schedule of a mesh whose 'pp' axis is larger than 1:
there the micro-batch loop (the tick scan) is OUTSIDE and a stage's layers
are inside each tick, because the micro-batches are the pipeline's clock. A
one-stage mesh has no schedule and is refused here: HybridPipelineTrainer
scans the layers on the outside and maps the micro-batches inside
(hybrid.py ``blocks_one_stage``), so that a layer's weights are sliced out
of the stack once a step and not once a micro-batch.

Here the whole pipeline is ONE compiled SPMD computation:
  - transformer blocks' params are stacked into [pp, layers_per_stage, ...]
    (or [pp, v, layers_per_virtual, ...] when interleaved) with the stage
    axis sharded over mesh axis 'pp' (shard_map manual);
  - microbatches stream through stages with lax.ppermute — the XLA
    collective-permute that replaces the reference's per-microbatch
    ncclSend/ncclRecv (send_v2_op.cu.cc);
  - the schedule loop is a lax.scan, so forward AND backward of the whole
    schedule differentiate through the permute chain — no per-stage
    hand-written backward passes (section_worker.cc:77-93);
  - other mesh axes (dp/tp/sp) stay in GSPMD 'auto' mode inside the stage
    body, composing pipeline with tensor/data parallelism.

Schedules:
  - v_virtual=1: GPipe fill-drain — n_micro + pp - 1 ticks of a full
    stage's layers each; bubble fraction (pp-1)/(n_micro+pp-1).
  - v_virtual=v>1: interleaved/circular (each device owns v non-contiguous
    "virtual stages"; microbatches circle the ring v times) —
    v·n_micro + pp - 1 ticks of 1/v the work each; bubble fraction
    (pp-1)/(v·n_micro+pp-1), i.e. v× smaller than GPipe AND than the
    reference's F-then-B. Requires n_micro >= pp.

Loss egress: when ``head_fn`` is given, the loss head runs INSIDE the
manual region, and the stages share it. Only the last stage writes its
buffer of finished micro-batches (the others' stay zeros), so after the
tick scan it is dealt out along the micro-batch axis by one reduce-scatter
of the buffers over 'pp' (``1/pp`` of the real one leaves the last stage
for each other stage), every stage runs the head on its own ``n_micro /
pp`` micro-batches against the matching rows of ``head_batch``, and two
scalars a term of the loss cross 'pp' back (sum and count: the loss is the
whole batch's mean, ``_whole_batch_mean``). Differentiated, the head's
``dx`` of each share returns by the transpose, an all-gather, of which the
last stage's ticks take their micro-batch each and the other stages' none,
and the head weights' cotangent is summed over 'pp' once by the region's
own transpose of a replicated input, each stage adding its share. Where
``n_micro`` is not a multiple of ``pp`` the shares would be
unequal: every stage then runs the head on its whole buffer in lockstep and
the last stage's value is kept (stage ``pp - 1`` does all of the head's work
and the others wait for it). Which form a program compiled is counted at
trace time in ``head/pp_share_traces{stages=<pp or 1>}``. Without head_fn
the full activation buffer is shared by a psum of the buffers (needed by
the manual-sp composition, where the head must see the sp-sharded output).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..profiler.trace import annotate as _annotate


def stack_block_params(block_param_lists):
    """[{name: val} per layer] → {name: [L, ...] stacked}."""
    names = list(block_param_lists[0].keys())
    return {n: jnp.stack([bp[n] for bp in block_param_lists], 0)
            for n in names}


def pipeline_apply(mesh: Mesh, stage_fn: Callable, stacked_params: Any,
                   x, n_micro: int, pp_axis: str = "pp",
                   sp_axis: str = None, v_virtual: int = 1,
                   head_fn: Optional[Callable] = None,
                   head_args: tuple = (), head_batch: tuple = (),
                   stage_aux: bool = False):
    """Run x [batch, ...] through the pipelined stacked blocks.

    stage_fn(params_one_chunk, x_mb) -> y_mb applies one (virtual) stage's
    layers to one microbatch. stacked_params leaves are [pp, ...] for
    v_virtual=1 or [pp, v, ...] for interleaved; x is split into n_micro
    microbatches along dim 0.

    head_fn(out_rows, *head_args, *batch_rows): optional loss head computed
    inside the region (see module docstring); pipeline_apply then returns
    the scalar loss instead of the activations. ``out_rows`` are the
    finished activations of some whole micro-batches (all of them, or a
    stage's ``1/pp``), ``batch_rows`` the same rows of every array in
    ``head_batch`` (leading dimension x's batch: tokens, labels);
    ``head_args`` (the head's weights) arrive whole. It returns the loss as
    a scalar, a mean over its rows in which every row weighs the same, or
    as a sequence of ``(mean, count)`` terms that add up to it, each a mean
    over ``count`` kept labels: what the whole batch's mean needs where
    the count differs between rows (an ``ignore_index``).

    stage_aux: when True, stage_fn returns ``(y_mb, aux)`` — a
    per-microbatch auxiliary scalar (e.g. the MoE load-balance loss of the
    stage's blocks) or a pytree of float32 arrays (that loss and the
    stage's counts beside it). Aux values are accumulated over the ticks
    where the stage holds REAL data (fill/drain garbage ticks masked out),
    psum'd over 'pp' so every stage's layers contribute, and averaged over
    microbatches, leaf by leaf. pipeline_apply then returns ``(out, aux)``.

    sp_axis: when set (sequence parallelism composed with pipeline), the
    shard_map is manual over BOTH axes — x's seq dim (dim 1) stays sharded
    over sp_axis and stage_fn sees the local sequence shard (its attention
    must then run the in-context ring, see models/gpt.py). Nested
    shard_maps over the same axis are rejected by the partitioner, so
    manual-over-both is the composition mechanism.
    """
    pp = mesh.shape.get(pp_axis, 1)
    v = v_virtual
    if sp_axis is not None and mesh.shape.get(sp_axis, 1) <= 1:
        sp_axis = None
    if sp_axis is not None and head_fn is not None:
        raise ValueError("head_fn is not supported under manual sp "
                         "(the head must see the sp-sharded output)")
    if v > 1 and n_micro < pp:
        raise ValueError(
            f"interleaved schedule needs n_micro >= pp ({n_micro} < {pp})")
    if pp == 1:
        raise ValueError(
            f"pipeline_apply schedules micro-batches over mesh axis "
            f"{pp_axis!r} of size > 1; a one-stage mesh has no schedule "
            f"(HybridPipelineTrainer scans the layers there, micro-batches "
            f"inside: hybrid.py blocks_one_stage)")
    compute_dtype = x.dtype
    # XLA:CPU's AllReducePromotion pass crashes on bf16 all-reduce; the
    # shard_map TRANSPOSE of a replicated input inserts exactly that (psum
    # of input cotangents over pp). Promote the boundary dtype on CPU only;
    # TPU keeps native bf16 transfers.
    from ..core.place import target_platform
    boundary_f32 = (target_platform() == "cpu"
                    and compute_dtype == jnp.bfloat16)

    param_specs = jax.tree_util.tree_map(
        lambda _: P(pp_axis), stacked_params)
    manual = frozenset({pp_axis} if sp_axis is None else {pp_axis, sp_axis})
    # params are pp-sharded but REPLICATED over sp: the shard_map
    # transpose psums their cotangents over the replicated axis —
    # promote that boundary too on CPU (same XLA:CPU bf16-collective
    # crash as above; TPU unaffected).
    param_f32 = boundary_f32 and sp_axis is not None

    def _pf(a):
        return a.astype(jnp.float32) if (param_f32
                                         and a.dtype == jnp.bfloat16) else a
    # xs is [n_micro, mb, seq, ...]: seq (dim 2) sharded over sp when set
    x_spec = P() if sp_axis is None else P(None, None, sp_axis)
    out_spec = P() if head_fn is not None else x_spec
    if stage_aux:
        out_spec = (out_spec, P())
    # head params/batch enter as explicit inputs (replicated over the
    # manual axes; their dp/tp shardings ride the auto axes) — closures
    # over outer-traced sharded values are rejected inside shard_map
    head_in = (tuple(head_args), tuple(head_batch))
    head_specs = jax.tree_util.tree_map(lambda _: P(), head_in)
    # the stages share the head where the micro-batches divide among them
    shares = pp if head_fn is not None and n_micro % pp == 0 else 1
    if head_fn is not None:
        from ..profiler import metrics
        metrics.registry().counter(
            "head/pp_share_traces{stages=%d}" % shares).add(1)
        for a in head_batch:
            if a.ndim == 0 or a.shape[0] != x.shape[0]:
                raise ValueError(
                    f"head_batch holds an array of shape {a.shape}: its "
                    f"leading dimension is not the batch's {x.shape[0]}")

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(param_specs, x_spec, head_specs), out_specs=out_spec,
             check_vma=False, axis_names=manual)
    def pipelined(params, xs, head_in):
        # params leaves: [1, ...] local slice; xs: [n_micro, mb, ...]
        local = jax.tree_util.tree_map(
            lambda a: a[0].astype(compute_dtype)
            if (param_f32 and a.dtype == jnp.float32
                and compute_dtype == jnp.bfloat16) else a[0], params)
        stage = jax.lax.axis_index(pp_axis)
        n_ticks = v * n_micro + pp - 1
        mb_shape = xs.shape[1:]
        # carry dtype: f32 on CPU+bf16 so the inter-stage ppermute (a
        # collective inside the manual region) never runs in bf16
        carry_dtype = jnp.float32 if boundary_f32 else compute_dtype
        state0 = jnp.zeros(mb_shape, carry_dtype)
        outputs0 = jnp.zeros(xs.shape, carry_dtype)
        # circuit-return buffer (interleaved: finished circuits wait here
        # until stage 0 re-injects them); unused for v == 1
        ret0 = jnp.zeros(xs.shape, carry_dtype)
        aux0 = jnp.zeros((), jnp.float32)
        if stage_aux:
            # the shapes of the stage's aux, from an abstract trace
            aux0 = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, jnp.float32), jax.eval_shape(
                    stage_fn, local if v == 1 else jax.tree_util.tree_map(
                        lambda a: a[0], local),
                    state0.astype(compute_dtype))[1])

        def tick(carry, t):
            prev_out, ret, outputs, aux_acc = carry
            # stage i receives stage i-1's last output (ring; stage 0's
            # recv feeds the circuit-return buffer)
            # pp/* named scopes: schedule-phase names baked into the
            # compiled program so device traces attribute time to the
            # inter-stage permute vs the stage compute (profiler/trace.py)
            with _annotate("pp/ppermute"):
                recv = jax.lax.ppermute(
                    prev_out, pp_axis,
                    [(i, (i + 1) % pp) for i in range(pp)])
            if v > 1:
                # a completed circuit item arrives back at stage 0 at tick
                # t with microbatch id (t - pp) mod n_micro
                ret_idx = jnp.clip((t - pp) % n_micro, 0, n_micro - 1)
                cur_ret = jax.lax.dynamic_index_in_dim(
                    ret, ret_idx, 0, keepdims=False)
                ret = jax.lax.dynamic_update_index_in_dim(
                    ret, jnp.where((stage == 0) & (t >= pp), recv, cur_ret),
                    ret_idx, 0)
            # stage 0 at tick t processes (circuit c, microbatch m)
            mb_idx = jnp.clip(t % n_micro, 0, n_micro - 1) if v > 1 else \
                jnp.clip(t, 0, n_micro - 1)
            circuit0 = t // n_micro if v > 1 else jnp.zeros_like(t)
            fresh = jax.lax.dynamic_index_in_dim(
                xs, mb_idx, 0, keepdims=False).astype(carry_dtype)
            if v > 1:
                returned = jax.lax.dynamic_index_in_dim(
                    ret, mb_idx, 0, keepdims=False)
                stage0_in = jnp.where(circuit0 == 0, fresh, returned)
            else:
                stage0_in = fresh
            inp = jnp.where(stage == 0, stage0_in, recv)
            # params for this tick: the circuit this stage is working on
            if v > 1:
                c_s = jnp.clip((t - stage) // n_micro, 0, v - 1)
                chunk = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, c_s, 0, keepdims=False), local)
            else:
                chunk = local
            with _annotate("pp/stage"):
                out = stage_fn(chunk, inp.astype(compute_dtype))
            if stage_aux:
                out, aux = out
                # fill/drain ticks run on garbage zeros — mask their aux.
                # stage s holds real data from tick s to s + v*n_micro - 1.
                busy = (t >= stage) & (t < stage + v * n_micro)
                aux_acc = jax.tree_util.tree_map(
                    lambda acc, a: acc + jnp.where(
                        busy, a.astype(jnp.float32), 0.0), aux_acc, aux)
            out = out.astype(carry_dtype)
            # the last stage finishing the LAST circuit produces output;
            # every other stage's buffer stays zeros, so a sum over 'pp'
            # of the buffers (or of parts of them) is the real one
            done_t = t - (pp - 1) - (v - 1) * n_micro
            out_idx = jnp.clip(done_t % n_micro if v > 1 else done_t,
                               0, n_micro - 1)
            valid = (done_t >= 0) & (stage == pp - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                               keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid, out, cur), out_idx, 0)
            return (out, ret, outputs, aux_acc), None

        (last, _, outputs, aux_acc), _ = jax.lax.scan(
            tick, (state0, ret0, outputs0, aux0), jnp.arange(n_ticks))
        # every stage's layers contribute their own aux; per-microbatch mean
        aux_total = jax.tree_util.tree_map(
            lambda a: a / n_micro, jax.lax.psum(aux_acc, pp_axis)) \
            if stage_aux else None
        if sp_axis is not None and stage_aux:
            # local routing groups per sp shard: average their aux
            aux_total = jax.lax.pmean(aux_total, sp_axis)
        if head_fn is not None:
            head_args, head_batch = head_in
            if shares > 1:
                # stage s takes micro-batches [s, s + 1) * n_micro / pp
                # and their rows of the batch; every share is real. The
                # exchange is the head's: under its scope name, so that
                # a device trace charges the head for the bytes it moves
                with _annotate("fwd/head"):
                    outputs = jax.lax.psum_scatter(
                        outputs, pp_axis, scatter_dimension=0, tiled=True)
                head_batch = tuple(
                    jax.lax.dynamic_index_in_dim(
                        a.reshape((pp, a.shape[0] // pp) + a.shape[1:]),
                        stage, 0, keepdims=False) for a in head_batch)
            rows = outputs.reshape((outputs.shape[0] * outputs.shape[1],)
                                   + tuple(outputs.shape[2:]))
            with _annotate("pp/head"):
                loss = _whole_batch_mean(
                    head_fn(rows.astype(compute_dtype), *head_args,
                            *head_batch),
                    shares > 1 or stage == pp - 1, pp_axis)
            return (loss, aux_total) if stage_aux else loss
        # share the whole buffer (float32 already where boundary_f32)
        shared = jax.lax.psum(outputs, pp_axis)
        return (shared, aux_total) if stage_aux else shared

    mbs = _to_microbatches(x, n_micro)
    if boundary_f32:
        mbs = mbs.astype(jnp.float32)
    if param_f32:
        stacked_params = jax.tree_util.tree_map(_pf, stacked_params)
    out = pipelined(stacked_params, mbs, head_in)
    aux = None
    if stage_aux:
        out, aux = out
    if head_fn is None:
        out = _from_microbatches(out, x.shape).astype(compute_dtype)
    return (out, aux) if stage_aux else out


def _whole_batch_mean(terms, real, pp_axis):
    """The loss of the whole batch from what ``head_fn`` returned on this
    stage's rows, or (``real`` false) on a buffer that is not the
    pipeline's output: each term's means weighed by their counts,
    ``sum_pp(mean * count) / sum_pp(count)``, so that a label counts the
    same whichever stage's share it fell into and the gradient's ``1/n``
    is the whole batch's. A scalar is one term whose every share counts
    the same."""
    if not isinstance(terms, (tuple, list)):
        terms = ((terms, 1.0),)
    counts = [jnp.where(real, jnp.asarray(c, jnp.float32), 0.0)
              for _, c in terms]
    sums = [jnp.where(real, m.astype(jnp.float32) * c, 0.0)
            for (m, _), c in zip(terms, counts)]
    sums, counts = jax.lax.psum((sums, counts), pp_axis)
    return sum(s / jnp.where(c > 0, c, 1.0) for s, c in zip(sums, counts))


def _to_microbatches(x, n_micro):
    b = x.shape[0]
    assert b % n_micro == 0, f"batch {b} not divisible into {n_micro} micro"
    return x.reshape((n_micro, b // n_micro) + tuple(x.shape[1:]))


def _from_microbatches(mbs, orig_shape):
    return mbs.reshape(orig_shape)
