"""Strategy compiler: DistributedStrategy → ONE pjit'd SPMD train step.

TPU-native replacement for the reference's meta-optimizer chain
(reference: fleet/base/strategy_compiler.py:1-211 + meta_optimizers/* —
which rewrite per-rank ProgramDescs, insert c_broadcast/c_allreduce ops,
prune non-owned optimizer ops, etc.). Here the same user intent — dp/tp/pp
degrees, ZeRO stage, AMP, recompute — is compiled into sharding
annotations on ONE program; GSPMD inserts every collective the reference
inserted by hand (SURVEY.md §7):

  ShardingOptimizer (ZeRO-2)  → optimizer state sharded over 'dp'
                                (weight-update sharding; grads become
                                reduce-scatter + update + all-gather)
  stage-3 (new vs reference)  → params sharded over 'dp'; XLA schedules
                                gather/release around use sites
  TP split                    → PartitionSpecs carried by parallel layers
  AMP                         → bf16 compute params, fp32 master + moments
  Recompute                   → jax.checkpoint policy on the forward
  grad allreduce (DP)         → implicit: mean loss over dp-sharded batch
"""
from __future__ import annotations

import math
import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework.tensor import Tensor
from ..nn import ClipGradByGlobalNorm
from ..profiler import instrument as _pinstr
from ..profiler import recompile as _precomp
from ..profiler import trace as _ptrace
from ..profiler.metrics import registry as _preg
from ..static.functional import functional_call, state_tensors
from .fleet.distributed_strategy import DistributedStrategy
from .mesh import create_mesh


def build_mesh_from_strategy(strategy: DistributedStrategy,
                             devices=None) -> Mesh:
    """hybrid_configs degrees → Mesh with axes (dp, pp, tp, sp, ep)."""
    devs = list(devices if devices is not None else jax.devices())
    h = strategy.hybrid_configs
    tp = max(1, h.mp_degree)
    pp = max(1, h.pp_degree)
    sp = max(1, h.sp_degree)
    ep = max(1, getattr(h, "ep_degree", 1))
    dp = h.dp_degree if h.dp_degree > 0 else \
        len(devs) // (tp * pp * sp * ep)
    axes = {"dp": dp, "pp": pp, "tp": tp, "sp": sp}
    if ep > 1:
        axes["ep"] = ep
    return create_mesh(axes, devs)


def _spec_axes(spec: P) -> set:
    used = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return used


def _add_axis(spec: P, ndim: int, shape, axis_name: str, axis_size: int) -> P:
    """Extend `spec` by sharding `axis_name` onto the first free, divisible
    dim (for ZeRO param/opt-state sharding). Returns spec unchanged if no
    dim qualifies."""
    if axis_size <= 1 or axis_name in _spec_axes(spec):
        return spec
    entries = list(spec) + [None] * (ndim - len(spec))
    for d in range(ndim):
        e = entries[d]
        existing = () if e is None else (e if isinstance(e, tuple) else (e,))
        # callers pass `shape` already divided by the existing sharding, so
        # this check covers divisibility under composition too
        if shape[d] % axis_size != 0:
            continue
        entries[d] = tuple(existing) + (axis_name,) if existing else axis_name
        return P(*entries)
    return spec


def resolve_param_specs(layer, mesh: Mesh, zero_stage: int = 0
                        ) -> Dict[str, P]:
    """Collect PartitionSpecs: TP specs from layers' ``param_shardings``
    (distributed/parallel_layers.py), plus ZeRO-3 dp sharding."""
    pn, pt, _, _ = state_tensors(layer)
    specs = {name: P() for name in pn}
    for lname, sub in layer.named_sublayers(include_self=True):
        ps = getattr(sub, "param_shardings", None)
        if not ps:
            continue
        for local, spec in ps.items():
            gname = f"{lname}.{local}" if lname else local
            if gname in specs:
                # drop axes absent from the mesh (e.g. tp on a dp-only mesh)
                entries = []
                for e in spec:
                    if e is None:
                        entries.append(None)
                    elif isinstance(e, (tuple, list)):
                        kept = tuple(a for a in e if a in mesh.axis_names
                                     and mesh.shape[a] > 1)
                        entries.append(kept if kept else None)
                    else:
                        entries.append(e if e in mesh.axis_names
                                       and mesh.shape[e] > 1 else None)
                specs[gname] = P(*entries)
    if zero_stage >= 3 and "dp" in mesh.axis_names:
        dp = mesh.shape["dp"]
        name2tensor = dict(zip(pn, pt))
        for name in specs:
            t = name2tensor[name]
            # keep divisibility under existing tp sharding
            shape = _local_check_shape(t._value.shape, specs[name], mesh)
            specs[name] = _add_axis(specs[name], t._value.ndim, shape,
                                    "dp", dp)
    return specs


def _local_check_shape(shape, spec: P, mesh: Mesh):
    """Shape divided by existing sharding, for divisibility checks."""
    out = list(shape)
    for d, e in enumerate(spec):
        if e is None:
            continue
        axes = e if isinstance(e, (tuple, list)) else (e,)
        for a in axes:
            out[d] = out[d] // mesh.shape[a]
    return tuple(out)


def functional_clip(clip, grads):
    """Apply a grad-clip object to a pytree of gradients (traced-safe).
    Mirrors the eager apply_grad_clip (optimizer/clip.py) for the compiled
    path; supports all three reference clip types (fluid/clip.py)."""
    from ..nn import ClipGradByNorm, ClipGradByValue

    if clip is None:
        return grads
    leaves = jax.tree_util.tree_leaves(grads)
    if isinstance(clip, ClipGradByValue):
        return jax.tree_util.tree_map(
            lambda g: jnp.clip(g, clip.min, clip.max), grads)
    if isinstance(clip, ClipGradByNorm):
        def per_leaf(g):
            n = jnp.linalg.norm(g.astype(jnp.float32).reshape(-1))
            s = jnp.minimum(1.0, clip.clip_norm / jnp.maximum(n, 1e-12))
            return (g * s).astype(g.dtype)

        return jax.tree_util.tree_map(per_leaf, grads)
    if isinstance(clip, ClipGradByGlobalNorm):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in leaves))
        scale = jnp.minimum(1.0, clip.clip_norm / jnp.maximum(gn, 1e-12))
        return jax.tree_util.tree_map(lambda g: (g * scale).astype(g.dtype),
                                      grads)
    raise TypeError(f"Unknown grad clip type: {type(clip)}")


def make_param_update(opt):
    """Shared per-param functional update: l2/decoupled decay + opt rule.
    Used by both compiled trainers so the semantics can't drift from the
    eager Optimizer.step fused loop."""
    decay_mode = opt._decay_mode
    l2 = opt._weight_decay

    def upd(p, g, s, lr, step_no, plr=1.0, wd=0.0):
        g = g.astype(jnp.float32)
        if decay_mode == "l2" and l2:
            g = g + l2 * p
        return opt._update(p, g, s, lr * plr, step_no, wd=wd)

    return upd


def make_flat_update(opt):
    """ZeRO flat-chunk spelling of :func:`make_param_update`: the same
    decay rule + opt rule applied to the fused flat parameter slice a
    dp shard owns (qcomm.dp_zero_step). Exact by construction — every
    optimizer ``_update`` is elementwise, so updating a slice of the
    concatenation bitwise-equals slicing the per-param updates; ``plr``
    / ``wd`` arrive as scalars or per-element vectors laid out like the
    flat buffer and broadcast elementwise either way."""
    decay_mode = opt._decay_mode
    l2 = opt._weight_decay

    def upd(p, g, s, lr, step_no, plr, wd):
        g = g.astype(jnp.float32)
        if decay_mode == "l2" and l2:
            g = g + l2 * p
        return opt._update(p, g, s, lr * plr, step_no, wd=wd)

    return upd


class _FlatShim:
    """Stand-in 'parameter' handed to ``optimizer._init_state`` to
    allocate state at the ZeRO flat-slab shape (state init only reads
    ``._value``)."""

    def __init__(self, value):
        self._value = value


def _flat_knob(vals, sizes, pad_to):
    """Per-parameter scalars -> the dp_zero_step knob spelling: one
    scalar when uniform, else a flat f32 vector laid out exactly like
    the fused param buffer (zero-padded tail; pad elements get knob 0,
    which is inert — their grads are padding zeros too)."""
    vals = [float(v) for v in vals]
    if len(set(vals)) <= 1:
        return jnp.float32(vals[0] if vals else 0.0)
    vec = np.concatenate([np.full(s, v, np.float32)
                          for v, s in zip(vals, sizes)]) \
        if sizes else np.zeros(0, np.float32)
    vec = np.pad(vec, (0, pad_to - vec.size))
    return jnp.asarray(vec)


class HybridParallelTrainer:
    """Compiled SPMD training loop over (model, optimizer, strategy).

    State (params/opt-states/buffers) lives on device with its sharding;
    ``sync_to_layer()`` writes it back into the eager Layer for
    checkpointing/eval.
    """

    def __init__(self, layer, optimizer, strategy: Optional[
            DistributedStrategy] = None, mesh: Optional[Mesh] = None,
            loss_fn=None, data_spec: Optional[Tuple] = None,
            donate: bool = True, accumulate_steps: int = 1,
            dp_grad_comm: str = "f32", dp_grad_block: int = 2048,
            dp_param_comm: Optional[str] = None):
        self.layer = layer
        self.optimizer = optimizer
        # gradient merge (reference: fleet gradient_merge meta-optimizer /
        # GradMergeOptimizer): the compiled step lax.scans over
        # ``accumulate_steps`` micro-batches — each micro's backward
        # completes before the next forward (one micro's activations
        # live at a time) — and applies ONE optimizer update on the
        # mean gradient. Amortizes the optimizer-state memory traffic,
        # which dominates for expert-heavy models (round-5 MoE profile:
        # AdamW moments on 508M params cost ~12% of the step).
        self.accumulate_steps = int(accumulate_steps)
        self.strategy = strategy or DistributedStrategy()
        self.mesh = mesh if mesh is not None else \
            build_mesh_from_strategy(self.strategy)
        self.loss_fn = loss_fn
        zero = self.strategy.sharding_configs.sharding_stage if \
            self.strategy.sharding else 0
        self.zero_stage = zero
        self.amp = self.strategy.amp
        # quantized DP-gradient sync (distributed/qcomm.py, ROADMAP 3b):
        # "int8" computes per-shard local gradients inside an all-manual
        # shard_map and reduces them through the EQuARX-style compressed
        # ring (blockwise int8 transport, f32 accumulation) instead of
        # GSPMD's implicit f32 AllReduce. Pure-DP only: every non-dp
        # mesh axis must be 1.
        from . import qcomm as _qcomm

        _qcomm.validate_dp_grad_comm(dp_grad_comm, self.mesh,
                                     zero_stage=zero,
                                     block=int(dp_grad_block))
        self.dp_grad_comm = dp_grad_comm
        self.dp_grad_block = int(dp_grad_block)

        # ZeRO-1/2 manual weight-update sharding (ISSUE 19; Xu et al.
        # 2004.13336): on a pure-DP mesh, stages 1-2 run the whole
        # update inside the ONE dp shard_map — reduce-scatter grads to
        # their owner shard (quantized or f32 ring per dp_grad_comm),
        # optimizer update on only the owned flat slice (state lives
        # at shard shape: the memory win), all-gather updated params
        # back (payload per dp_param_comm). Non-pure-DP meshes keep
        # the GSPMD _add_axis spelling below; stage 3 (param sharding)
        # is GSPMD-only.
        dp = self.mesh.shape.get("dp", 1)
        pure_dp = all(s == 1 for a, s in self.mesh.shape.items()
                      if a != "dp")
        self.zero_manual = bool(zero in (1, 2) and dp > 1 and pure_dp)
        if dp_param_comm is None:
            dp_param_comm = "bf16" if (self.zero_manual
                                       and dp_grad_comm == "int8") \
                else "f32"
        _qcomm.validate_dp_param_comm(dp_param_comm, self.zero_manual)
        self.dp_param_comm = dp_param_comm
        if self.zero_manual:
            clip = optimizer._grad_clip
            if clip is not None and not isinstance(clip,
                                                   ClipGradByGlobalNorm):
                raise NotImplementedError(
                    "ZeRO sharded update supports grad clipping only "
                    "by global norm (per-leaf clips need the full "
                    f"gradient on every shard); got {type(clip).__name__}")

        pn, pt, bn, bt = state_tensors(layer)
        self.param_names, self._param_tensors = pn, pt
        self.buffer_names, self._buffer_tensors = bn, bt
        self.param_specs = resolve_param_specs(layer, self.mesh, zero)

        if self.zero_manual:
            # fused flat optimizer state, dp-sharded: ONE [dp*chunk]
            # slab per state key (+ the f32 master param copy when the
            # param all-gather is compressed — bf16 round-trip rounding
            # would swallow small updates without it)
            sizes = [int(np.prod(p._value.shape)) for p in pt]
            self._zero_sizes = sizes
            self._zero_chunk = _qcomm.zero_chunk_len(
                sum(sizes), dp, self.dp_grad_block)
            slab = dp * self._zero_chunk
            st = optimizer._init_state(
                _FlatShim(jnp.zeros((slab,), jnp.float32)))
            if self.dp_param_comm != "f32":
                flat = np.concatenate(
                    [np.asarray(p._value, np.float32).reshape(-1)
                     for p in pt]) if pt else np.zeros(0, np.float32)
                st["master"] = jnp.asarray(
                    np.pad(flat, (0, slab - flat.size)))
            dp_sh = NamedSharding(self.mesh, P("dp"))
            self.opt_states = {k: jax.device_put(v, dp_sh)
                               for k, v in st.items()}
            self.opt_specs = {k: P("dp") for k in st}
        else:
            # optimizer state: init + specs (GSPMD ZeRO>=1 shards
            # moments over dp via _add_axis)
            self.opt_states = []
            self.opt_specs = []
            for name, p in zip(pn, pt):
                s = optimizer._init_state(p)
                self.opt_states.append(s)
                pspec = self.param_specs[name]
                if zero >= 1:
                    shape = _local_check_shape(p._value.shape, pspec,
                                               self.mesh)
                    sspec = _add_axis(pspec, p._value.ndim, shape, "dp",
                                      dp)
                else:
                    sspec = pspec
                self.opt_specs.append({k: sspec for k in s})

        # place state onto the mesh
        self.params = [
            jax.device_put(p._value, NamedSharding(self.mesh,
                                                   self.param_specs[n]))
            for n, p in zip(pn, pt)]
        self.buffers = [jax.device_put(b._value,
                                       NamedSharding(self.mesh, P()))
                        for b in bt]
        if not self.zero_manual:
            self.opt_states = jax.device_put(
                self.opt_states,
                [{k: NamedSharding(self.mesh, spec[k]) for k in spec}
                 for spec in self.opt_specs])

        self.data_spec = data_spec
        self._step = 0
        self._prof_site = _precomp.unique_site("compile_train_step")
        self._build()

    # -- functional pieces -------------------------------------------------
    def _forward_loss(self, params, buffers, batch, key):
        layer = self.layer
        if self.amp:
            cast = [v.astype(jnp.bfloat16)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v
                    for v in params]
        else:
            cast = params
        if self.amp:
            # inputs follow the compute dtype (conv/matmul require matching
            # operand dtypes); int arrays pass through, and in the loss_fn
            # regime the LABEL (last element) keeps its dtype — float
            # regression/soft-label targets must not be rounded to bf16
            n_cast = len(batch) - 1 if self.loss_fn is not None \
                else len(batch)
            batch = tuple(
                b.astype(jnp.bfloat16)
                if i < n_cast and jnp.issubdtype(
                    jnp.asarray(b).dtype, jnp.floating)
                else b for i, b in enumerate(batch))
        # Pallas kernels in the model nest a shard_map over the mesh axes
        # that are GSPMD-auto here (all of them on the pure-GSPMD path,
        # none inside qcomm's all-manual dp wrap): XLA does not
        # partition a Mosaic call
        from . import context as dctx

        with dctx.kernel_scope(self.mesh):
            if self.loss_fn is not None:
                with _ptrace.annotate("fwd"):
                    out, new_buf = functional_call(layer, cast, buffers,
                                                   batch[:-1], training=True,
                                                   rng_key=key)
                    loss = self.loss_fn(
                        Tensor(out) if not isinstance(out, Tensor) else out,
                        Tensor(batch[-1]))
                loss = loss._value if isinstance(loss, Tensor) else loss
            else:
                # model exposes .loss(*batch) (e.g. GPT)
                from ..core import rng as rng_mod

                pt = self._param_tensors
                bt = self._buffer_tensors
                from ..static.functional import _swapped_state

                with _swapped_state(pt + bt, list(cast) + list(buffers)):
                    with rng_mod.key_scope(key), _ptrace.annotate("fwd"):
                        loss_t = layer.loss(*[Tensor(b) for b in batch])
                    new_buf = [t._value for t in bt]
                loss = loss_t._value
        return loss.astype(jnp.float32), new_buf

    def _build(self):
        opt = self.optimizer
        clip = opt._grad_clip
        mesh = self.mesh

        lrs = tuple(p.optimize_attr.get("learning_rate", 1.0)
                    for p in self._param_tensors)
        wds = tuple(opt._decoupled_wd(p) for p in self._param_tensors)
        upd = make_param_update(opt)

        k_acc = self.accumulate_steps

        def local_loss_grads(params, buffers, batch, key):
            """Loss + gradients over (this shard of) ``batch`` — the
            whole logical batch on the GSPMD path, the device-local
            shard inside the dp_grad_comm='int8' shard_map."""
            if k_acc > 1:
                for b in jax.tree_util.tree_leaves(batch):
                    if b.shape[0] % k_acc:
                        raise ValueError(
                            f"gradient merge: batch size {b.shape[0]} is "
                            f"not divisible by accumulate_steps={k_acc}"
                            + (" — the PER-SHARD batch: "
                               "dp_grad_comm='int8' scans micro-batches "
                               "inside each dp shard, so the global "
                               "batch must divide dp × accumulate_steps"
                               if qcomm_dp > 1 else ""))
                micros = jax.tree_util.tree_map(
                    lambda b: b.reshape((k_acc, b.shape[0] // k_acc)
                                        + b.shape[1:]), batch)
                keys = jax.random.split(key, k_acc)

                def micro(carry, xs):
                    bufs, acc = carry
                    mb, mkey = xs

                    def loss_of(ps):
                        return self._forward_loss(ps, bufs, mb, mkey)

                    (mloss, nbuf), g = jax.value_and_grad(
                        loss_of, has_aux=True)(params)
                    acc = [a + gi.astype(a.dtype)
                           for a, gi in zip(acc, g)]
                    return (nbuf, acc), mloss

                acc0 = [jnp.zeros(p.shape, jnp.float32) for p in params]
                (new_buf, acc), mlosses = jax.lax.scan(
                    micro, (buffers, acc0), (micros, keys))
                loss = jnp.mean(mlosses)
                grads = [a / k_acc for a in acc]
            else:
                def loss_of(ps):
                    loss, new_buf = self._forward_loss(ps, buffers, batch,
                                                       key)
                    return loss, new_buf

                (loss, new_buf), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params)
            return loss, new_buf, grads

        qcomm_dp = self.mesh.shape.get("dp", 1) \
            if self.dp_grad_comm == "int8" else 1
        qcomm_block = self.dp_grad_block
        zero_manual = self.zero_manual
        zdp = self.mesh.shape.get("dp", 1)
        if zero_manual:
            from . import qcomm as _zq

            flat_upd = make_flat_update(opt)
            clip_norm = float(clip.clip_norm) if clip is not None \
                else None
            slab = zdp * self._zero_chunk
            plr_knob = _flat_knob(lrs, self._zero_sizes, slab)
            wd_knob = _flat_knob(wds, self._zero_sizes, slab)

        def step_fn(params, opt_states, buffers, batch, lr, step_no, key):
            # trace-time side effect: reports every (re)trace of this
            # program with the triggering batch shapes (profiler.recompile)
            _precomp.mark_trace(self._prof_site, batch)
            if zero_manual:
                # ZeRO-1/2 sharded update: the ONE shared shard_map
                # wrap (qcomm.dp_zero_step) does per-shard local
                # grads, fused reduce-scatter (quantized or f32 ring
                # per dp_grad_comm), global-norm clip on the reduced
                # chunks, the shard-local flat optimizer update, and
                # the param all-gather (dp_param_comm payload). Grad
                # accumulation (local_loss_grads' scan) and AMP
                # compose unchanged — they live inside `local`.
                def local(rep, params_, key_, batch_):
                    (buffers_,) = rep
                    return local_loss_grads(params_, buffers_, batch_,
                                            key_)

                bspecs = tuple(self.data_spec) \
                    if self.data_spec is not None \
                    else _zq.dp_batch_specs(batch, zdp)
                loss, new_buf, new_params, new_states = _zq.dp_zero_step(
                    mesh, zdp, self.dp_grad_block, self.dp_grad_comm,
                    self.dp_param_comm, local, flat_upd, (buffers,),
                    params, opt_states, batch, bspecs, key, lr,
                    step_no, plr_knob, wd_knob, clip_norm=clip_norm)
                return loss, new_params, new_states, new_buf
            if qcomm_dp > 1:
                # quantized DP-grad sync: per-shard local grads inside
                # the ONE shared all-manual shard_map wrap (qcomm.py),
                # reduced by the EQuARX-style compressed ring. The
                # local loss is the mean over the shard, so
                # pmean(loss) == the global mean loss and pmean(local
                # grads) == its gradient — the quantized ring replaces
                # that pmean, which is the ONLY numeric difference vs
                # the GSPMD path. An explicit data_spec is
                # authoritative (a leaf the user replicated must NOT
                # be split just because its dim 0 happens to divide
                # dp — under the manual wrap that would hand each
                # shard a slice of a non-batch array); the lead-dim
                # heuristic covers the no-spec default.
                from . import qcomm as _qcomm

                def local(rep, key_, batch_):
                    params_, buffers_ = rep
                    return local_loss_grads(params_, buffers_, batch_,
                                            key_)

                bspecs = tuple(self.data_spec) \
                    if self.data_spec is not None \
                    else _qcomm.dp_batch_specs(batch, qcomm_dp)
                loss, new_buf, grads = \
                    _qcomm.dp_quantized_value_and_grads(
                        mesh, qcomm_dp, qcomm_block, local,
                        (params, buffers), batch, bspecs, key)
            else:
                loss, new_buf, grads = local_loss_grads(
                    params, buffers, batch, key)
            grads = functional_clip(clip, grads)
            with _ptrace.annotate("optim"):
                new_params, new_states = [], []
                for p, g, s, plr, wd in zip(params, grads, opt_states,
                                            lrs, wds):
                    np_, ns = upd(p, g, s, lr, step_no, plr=plr, wd=wd)
                    new_params.append(np_)
                    new_states.append(ns)
            return loss, new_params, new_states, new_buf

        param_sh = [NamedSharding(mesh, self.param_specs[n])
                    for n in self.param_names]
        if zero_manual:
            state_sh = {k: NamedSharding(mesh, P("dp"))
                        for k in self.opt_specs}
        else:
            state_sh = [{k: NamedSharding(mesh, spec[k]) for k in spec}
                        for spec in self.opt_specs]
        buf_sh = [NamedSharding(mesh, P()) for _ in self.buffers]
        repl = NamedSharding(mesh, P())

        self._out_shardings = (repl, param_sh, state_sh, buf_sh)
        self._step_fn = jax.jit(
            step_fn,
            in_shardings=(param_sh, state_sh, buf_sh, None, None, None,
                          None),
            out_shardings=self._out_shardings,
            donate_argnums=(0, 1))

    def _shard_batch(self, batch):
        arrs = []
        for i, b in enumerate(batch):
            v = b._value if isinstance(b, Tensor) else jnp.asarray(b)
            if self.data_spec is not None:
                spec = self.data_spec[i]
            else:
                spec = P("dp") if v.ndim >= 1 and \
                    v.shape[0] % self.mesh.shape.get("dp", 1) == 0 else P()
            arrs.append(jax.device_put(v, NamedSharding(self.mesh, spec)))
        return tuple(arrs)

    def step(self, *batch) -> float:
        """Run one compiled hybrid-parallel training step; returns loss."""
        from ..core import rng as rng_mod

        self._step += 1
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        step_no = jnp.asarray(self._step, jnp.int32)
        key = rng_mod.next_key()
        # disabled cost: one bool read. Enabled, the step is host-timed
        # against a loss value fetch and the train counters/memory
        # high-water are recorded.
        if _ptrace.is_enabled():
            t0 = time.perf_counter_ns()
            with _ptrace.scope("compiled/h2d"):
                batch = self._shard_batch(batch)
            with _ptrace.scope("compiled/step"):
                loss, self.params, self.opt_states, self.buffers = \
                    self._step_fn(self.params, self.opt_states,
                                  self.buffers, batch, lr, step_no, key)
                float(np.asarray(loss))
            reg = _preg()
            reg.counter("train/steps").add(1)
            reg.counter("train/tokens").add(_pinstr.tokens_in_batch(batch))
            reg.histogram("compiled/step_ms").observe(
                (time.perf_counter_ns() - t0) / 1e6)
            _pinstr.record_memory_high_water()
        else:
            batch = self._shard_batch(batch)
            loss, self.params, self.opt_states, self.buffers = \
                self._step_fn(self.params, self.opt_states, self.buffers,
                              batch, lr, step_no, key)
        self.optimizer._global_step = self._step
        return loss

    __call__ = step

    def aot_lower(self, *batch):
        """Lower the train step on the trainer's own (concrete) state
        without executing it — the program handed to XLA, for
        inspection. suppressed(): this re-trace is by design, not a
        silent recompile, and the training RNG stream is not advanced."""
        with _precomp.suppressed():
            return self._step_fn.lower(
                self.params, self.opt_states, self.buffers,
                self._shard_batch(batch), jnp.asarray(0.0, jnp.float32),
                jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0))

    def profile_step_phases(self, *batch, iters: int = 2,
                            trace_window: int = 0):
        """Per-phase (fwd/bwd/optim/comm) decomposition — the
        compile_train_step counterpart of
        ``HybridPipelineTrainer.profile_step_phases`` (see its docstring
        for semantics): nested prefixes fwd / fwd+bwd / full step are
        compiled and timed, comm is modeled from collective bytes, and
        the results land in the ``phase/*_ms`` gauges.
        ``trace_window=k`` wraps k more real steps in a parsed
        device-trace capture (measured per-op/per-collective timings,
        overlap fraction, MFU ledger) returned under ``"trace"``."""
        from ..core import rng as rng_mod

        vs = self._shard_batch(batch)
        key = rng_mod.next_key()

        fwd = jax.jit(lambda ps, bufs: self._forward_loss(
            ps, bufs, vs, key)[0])
        t_fwd = _pinstr.time_compiled(
            lambda: fwd(self.params, self.buffers), iters)
        fb = jax.jit(lambda ps, bufs: jax.value_and_grad(
            lambda p_: self._forward_loss(p_, bufs, vs, key),
            has_aux=True)(ps))
        t_fb = _pinstr.time_compiled(
            lambda: fb(self.params, self.buffers), iters)
        t_step = _pinstr.time_compiled(lambda: self.step(*batch), iters)

        lowered = self.aot_lower(*batch)
        st = _pinstr.record_collectives_from(lowered, self.mesh)
        # same program inventory + measured/estimated comm split as
        # HybridPipelineTrainer.profile_step_phases
        from ..profiler import xla_stats as _xstats

        ps = _xstats.record_lowered(self._prof_site, lowered)
        out = _pinstr.record_phases(
            fwd_s=t_fwd, fwdbwd_s=t_fb, step_s=t_step,
            comm_bytes=st["total_bytes"],
            platform=self.mesh.devices.flat[0].platform,
            cost_bytes_accessed=ps.bytes_accessed)
        if trace_window:
            from ..profiler import device_trace as _dtrace

            with _dtrace.capture(steps=int(trace_window),
                                 label=self._prof_site) as cap:
                for _ in range(int(trace_window)):
                    _pinstr._first_leaf(self.step(*batch))
            out["trace"] = cap.summary
        return out

    def memory_ledger(self) -> dict:
        """Per-rank resident bytes by state category, from ACTUAL array
        shardings (profiler.record_memory_ledger — gauges
        ``mem/{param,grad,opt_state,master}_bytes``). On the manual
        ZeRO path opt state (and master) are [dp*chunk] slabs sharded
        P('dp'), so their per-rank count is 1/dp of the replicated
        baseline; ``grad`` is the transient fused buffer — full-size
        pre-reduce-scatter on every path, counted at the per-rank peak
        (the full flat buffer; after the scatter only the owned chunk
        stays live)."""
        cats = {"param": self.params,
                "grad": 4 * sum(int(np.prod(np.shape(p)))
                                for p in self.params)}
        if self.zero_manual:
            cats["opt_state"] = {k: v for k, v in self.opt_states.items()
                                 if k != "master"}
            if "master" in self.opt_states:
                cats["master"] = self.opt_states["master"]
        else:
            cats["opt_state"] = self.opt_states
        return _pinstr.record_memory_ledger(cats)

    def device_state(self) -> dict:
        """Device-resident training state as a pytree for
        distributed/checkpoint.py (the HybridPipelineTrainer contract):
        arrays keep their shardings, so a dp-sharded ZeRO slab saves
        per-shard and restores back to P('dp') placement."""
        return {"params": list(self.params),
                "buffers": list(self.buffers),
                "opt": self.opt_states}

    def load_device_state(self, st: dict, step: Optional[int] = None):
        """Inverse of :meth:`device_state` (restore path)."""
        self.params = list(st["params"])
        self.buffers = list(st["buffers"])
        self.opt_states = st["opt"]
        if step is not None:
            self._step = int(step)
            self.optimizer._global_step = int(step)

    def sync_to_layer(self):
        """Write device state back into the eager Layer (for save/eval)."""
        for t, v in zip(self._param_tensors, self.params):
            t._value = v
        for t, v in zip(self._buffer_tensors, self.buffers):
            t._value = v
        # hand optimizer its state back (for state_dict)
        if self.zero_manual:
            # regather the flat dp-sharded slabs and slice them back
            # into per-param state (host-side; save/eval path only)
            flat = {k: np.asarray(v) for k, v in self.opt_states.items()
                    if k != "master"}
            off = 0
            for p, sz in zip(self._param_tensors, self._zero_sizes):
                shape = p._value.shape
                self.optimizer._accumulators[id(p)] = {
                    k: jnp.asarray(v[off:off + sz].reshape(shape))
                    for k, v in flat.items()}
                off += sz
        else:
            for p, s in zip(self._param_tensors, self.opt_states):
                self.optimizer._accumulators[id(p)] = s
        return self.layer


def compile_train_step(layer, optimizer, strategy=None, mesh=None,
                       loss_fn=None, **kw) -> HybridParallelTrainer:
    return HybridParallelTrainer(layer, optimizer, strategy, mesh, loss_fn,
                                 **kw)
