"""Model-agnostic hybrid-parallel trainer: dp × tp × pp × sp × ZeRO in ONE
pjit program.

The reference composes parallelism with a chain of meta-optimizers that
rewrite per-rank programs around USER model code (reference:
fleet/meta_optimizers/{sharding,pipeline,amp,recompute}_optimizer.py chained
by fleet/base/strategy_compiler.py; the pipeline splitter keys on per-op
device attributes, pipeline_optimizer.py:136) — model-agnostic by operating
on the program graph. Here the trainer is model-agnostic by a three-method
protocol any stacked-block model declares (models/gpt.py, models/bert.py):

  pipeline_stem(*batch)  -> activations       (embeddings)
  pipeline_blocks()      -> list of identical blocks (stackable params)
  pipeline_head(x, *batch) -> scalar loss     (norm + head + loss)

and, where the loss is a mean over labels some of which are ignored, a
fourth that says over how many, so that a head run on slices of the batch
(the pipeline's stages share it, pipeline.py "Loss egress") still gives the
whole batch's mean:

  pipeline_head_terms(x, *batch) -> ((mean, count), ...) adding up to
                                    pipeline_head's loss

The trainer stacks block params to [pp, layers_per_stage, ...], shards the
stage axis over 'pp' (pipeline.py shard_map), scans/unrolls layers within a
stage, shards batch dim 0 over 'dp' (+ seq dim 1 over 'sp'), applies ZeRO
1/2/3 by adding a 'dp' axis to opt-state/param shardings, bf16-casts under
amp, and wraps blocks in jax.checkpoint under recompute — all in one jitted
step XLA can schedule globally.

Which loop is outside is decided by the mesh, for every model
(``_forward_loss``):

  pp == 1: the layer scan outside, the micro-batch loop inside
      (``blocks_one_stage``). The scan hands one layer's parameters to
      its body and the block is mapped over the micro-batches there, so
      forward and backward each slice a layer out of the stack once a
      step, the backward sums ONE layer's gradient over the micro-batches
      and writes it into the stack once. With micro-batches outside,
      ``value_and_grad`` sliced every layer per micro-batch, built the
      whole stack's gradient per micro-batch and added stacks: a quarter
      of the OLMoE step, whose experts are 0.8 GB a layer (PERF.md,
      PR 29).
  pp > 1: the micro-batch loop outside — it is the pipeline's clock
      (pipeline.py's tick scan), a stage's layers inside each tick.

Both orders apply the same block to the same micro-batch with the same
weights, recompute the block of one micro-batch at a time, save x of every
(layer, micro-batch) between forward and backward, and sum in the same
order.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import contextlib
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.place import target_platform as _target_platform
from ..framework.tensor import Tensor
from ..profiler import instrument as _pinstr
from ..profiler import recompile as _precomp
from ..profiler import trace as _ptrace
from ..profiler.metrics import registry as _preg
from ..static.functional import _swapped_state, state_tensors
from .fleet.distributed_strategy import DistributedStrategy
from .pipeline import (_from_microbatches, _to_microbatches,
                       pipeline_apply)
from .strategy_compiler import (_add_axis, _local_check_shape,
                                build_mesh_from_strategy,
                                resolve_param_specs)


#: sentinel block_opt suffix carrying the fused ZeRO flat slabs (the
#: manual sharded-update path): one [dp*chunk] dp-sharded array per
#: optimizer-state key, riding the regular block_opt plumbing so
#: device_state / checkpointing / donation stay structure-agnostic
_ZERO_SLAB = "_zero_flat_"


def _check_protocol(model):
    for m in ("pipeline_stem", "pipeline_blocks", "pipeline_head"):
        if not hasattr(model, m):
            raise TypeError(
                f"{type(model).__name__} does not implement the pipeline "
                f"protocol ({m}); see distributed/hybrid.py docstring")


def _setup_phase(init):
    """The constructor inside phase ``setup/trainer`` [``site``], on the
    always-on record (profiler/trace.py ``phase``): its children are what
    it does, in order; ``site`` is the step program's dispatch site, the
    one its ``setup/first_call`` carries."""
    @functools.wraps(init)
    def __init__(self, *args, **kwargs):
        # recompilation telemetry: every (re)trace of this trainer's step
        # program is reported to profiler.recompile under this site
        self._prof_site = _precomp.unique_site("hybrid.step")
        with _ptrace.phase("setup/trainer", site=self._prof_site):
            init(self, *args, **kwargs)
    return __init__


class HybridPipelineTrainer:
    """Compiled hybrid-parallel trainer for any pipeline-protocol model."""

    @_setup_phase
    def __init__(self, model, optimizer,
                 strategy: Optional[DistributedStrategy] = None,
                 mesh: Optional[Mesh] = None, n_micro: Optional[int] = None,
                 v_virtual: Optional[int] = None,
                 remat_policy: Optional[str] = None,
                 param_dtype=None, moment_dtype=None,
                 offload_optimizer: bool = False,
                 offload_params: bool = False,
                 offload_depth: int = 2,
                 stream_layers: bool = False,
                 comp_resident: bool = True,
                 conservative_fetch: bool = False,
                 update_scan: bool = False,
                 unroll_layers: Optional[bool] = None,
                 free_eager: bool = False,
                 guard_bad_steps: bool = False,
                 dp_grad_comm: str = "f32",
                 dp_grad_block: int = 2048,
                 dp_param_comm: Optional[str] = None):
        """Memory knobs for billion-param single/few-chip configs
        (reference analogue: RecomputeConfig offload + ShardingConfig,
        distributed_strategy.proto:25-35):

        param_dtype:  storage dtype of the master params (default f32;
            'bfloat16' halves param memory — the update still computes
            in f32 and casts back).
        moment_dtype: storage dtype of optimizer moments (e.g.
            'bfloat16' halves AdamW state; update math stays f32).
        offload_optimizer: place optimizer state in pinned_host memory
            (the ZeRO-offload idea via XLA memory kinds). State streams
            host→HBM around the update each step — measured ~12 GB/s
            effective on a v5e host link, so this trades step time for
            HBM; use for models whose state cannot fit at any dtype.
        offload_params: ZeRO-Offload layout — the f32 master params live
            in pinned_host memory; each step streams them to HBM, casts
            to bf16 compute copies (grads are then bf16, halving grad
            HBM), and the f32 update streams master+moments through HBM
            per parameter group before writing back to host. Requires
            amp. This is the full-fidelity path for models whose f32
            master + f32 grads cannot fit HBM (1.3B+ on one 16 GB v5e).
        stream_layers: store host-offloaded state PER-LAYER and stream
            it through HBM behind a depth-``offload_depth``
            optimization_barrier chain (fetch layer k+1 ∥ f32 update
            layer k ∥ writeback layer k−1; the first fetches hide
            under forward/backward). With offload_params the forward
            runs on persistent bf16 compute copies, so per-step host
            traffic is one master read + one write. Bounds the HBM
            working set to ``offload_depth`` layers instead of a whole
            stacked group — the knob meant to fit 1.9B on one v5e
            (1.3B offload MFU 0.3955 → 0.4295 was measured in an
            earlier environment, not reproduced; see the ledger once
            there is one).
        comp_resident: (stream_layers) keep the bf16 compute copies as
            persistent trainer state (default). False re-streams the
            forward copies per-layer from host each step — a near-zero-
            HBM-argument program.
        conservative_fetch: (stream_layers) additionally gate host
            fetches on the layer's gradient: no fetch overlaps
            forward/backward, trading the overlap for a smaller peak
            (the 1.9B fit knob).
        unroll_layers: unroll the layer loop: the outer loop at pp == 1
            (the micro-batch loop inside it stays a loop), a stage's
            layers inside each tick at pp > 1. Default: unroll
            on TPU without remat (removes the scan's dynamic-slice
            bookkeeping), scan under remat — unrolling a rematerialized
            backward lets the latency-hiding scheduler hoist every
            layer's recomputation early, holding dozens of ffn
            intermediates live at once (measured 31% HBM fragmentation
            at 1.3B); the scan keeps layer backward strictly
            sequential so one layer's working set bounds live memory.
        free_eager: delete the eager model's device buffers after the
            trainer stacks/casts its own copies — at 1.3B the eager f32
            params are 5.3 GB of HBM that would sit dead next to the
            trainer's bf16 state. ``sync_to_layer`` restores them.

        Observability knobs (paddle_tpu.profiler; all zero-cost until
        ``profiler.enable()`` — the step reads one bool when disabled):

        profiler.enable(trace_dir=...): every ``step()`` then records an
            ``hybrid/h2d`` + ``hybrid/step`` host span (synced on the
            loss, so it measures execution, not dispatch), moves the
            ``train/steps`` / ``train/tokens`` counters and the
            ``hybrid/step_ms`` histogram, and tracks the device-memory
            high-water mark. An async-dispatch loop (elastic.py) sets
            ``profiled_step_sync = False`` to keep the profiled step
            from forcing the per-step sync it is hiding — the histogram
            is then honestly named ``hybrid/dispatch_ms`` and the
            deferred materializations record ``hybrid/sync_wait``;
            ``trace_dir`` additionally captures a
            TensorBoard-loadable XLA device trace. ``fwd/stem``,
            ``fwd/blocks``, ``fwd/head`` named scopes are baked into the
            compiled program, so XLA traces attribute device time per
            phase regardless of when profiling was switched on.
        profile_step_phases(*batch): fwd/bwd/optim/comm phase split as
            ``phase/*_ms`` gauges (two extra compiles; comm is modeled
            from collective bytes — see the method docstring).
        Resilience knob (paddle_tpu.resilience rides on it):

        guard_bad_steps: bake a finite check on the loss AND every
            clipped gradient leaf into the compiled step. A non-finite
            step keeps params and optimizer state bit-identical (the
            update is computed then deselected — momentum does not
            decay, weight decay does not apply), so one poisoned batch
            cannot touch the weights. ``last_step_ok`` reads the
            previous step's verdict (lazy device sync);
            ``inject_fault_scale(nan)`` poisons the NEXT step's loss —
            the deterministic NaN-gradient hook the chaos harness uses.
            Composes with ``offload_optimizer`` (the deselect runs on
            the device copies fetched for the update, so no state is
            double-streamed); unsupported with ``offload_params`` /
            ``stream_layers`` (the param select would force host-
            resident masters through HBM twice).

        retrace telemetry: every (re)trace of the step program is logged
            to ``profiler.retraces()`` with the triggering batch shapes;
            diagnostic lowerings (``aot_lower``/``memory_analysis``) are
            suppressed, so anything in the log is a silent recompile.
        profiler.summary()/export_chrome_trace(path): the collected
            picture — per-scope spans, counters, tokens/sec + steps/sec
            over the enabled window, phases, retraces."""
        _check_protocol(model)
        if getattr(getattr(model, "config", None), "loop_steps", 1) > 1:
            raise NotImplementedError(
                "HybridPipelineTrainer trains one pass of the stack: a "
                "model with loop_steps > 1 needs a scan over the steps "
                "around the layer scan, gradients summed over the steps and "
                "the exit-weighted loss (ROADMAP R1)")
        # MoE composes with pp: a block leaves its auxiliary loss, already
        # weighted, in ``block.aux_loss`` and its counts in
        # ``block.aux_stats``; pipeline_apply carries both across the
        # schedule (stage_aux) and the step hands the counts out
        # (``aux_stats``)
        cfg = getattr(model, "config", None)
        self.moe = bool(getattr(cfg, "moe_num_experts", 0))
        self.model = model
        self.optimizer = optimizer
        self.strategy = strategy or DistributedStrategy()
        self.mesh = mesh if mesh is not None else \
            build_mesh_from_strategy(self.strategy)
        self.pp = self.mesh.shape.get("pp", 1)
        self.n_micro = n_micro or max(
            self.strategy.pipeline_configs.accumulate_steps,
            self.strategy.pipeline_configs.micro_batch, self.pp)
        # interleaved/circular schedule degree (pipeline.py): v virtual
        # stages per device shrink the bubble v×
        self.v = v_virtual or getattr(self.strategy.pipeline_configs,
                                      "virtual_pipeline_degree", 1) or 1
        self.amp = self.strategy.amp
        self.remat = self.strategy.recompute
        # remat_policy "dots": selective remat — matmul outputs are saved,
        # elementwise/softmax recomputed. Most of full remat's memory win
        # at a fraction of its FLOP cost (full remat re-runs the matmuls
        # too, reference RecomputeOptimizer semantics).
        self.remat_policy = remat_policy
        self.zero = self.strategy.sharding_configs.sharding_stage \
            if self.strategy.sharding else 0
        self.param_dtype = jnp.dtype(param_dtype) if param_dtype else None
        self.moment_dtype = jnp.dtype(moment_dtype) if moment_dtype \
            else None
        self.offload_optimizer = offload_optimizer
        self.offload_params = offload_params
        # host↔HBM streaming pipeline depth: how many per-group f32
        # (p, m, v) working sets may be in flight at once. Deeper = more
        # copy/compute overlap, +1 group of transient HBM per step
        self.offload_depth = max(1, int(offload_depth))
        # update_scan: run the stacked-group optimizer update as a
        # lax.scan over layers — bounds f32 update transients to one
        # layer instead of a whole group. Opt-in: the default keeps the
        # validated whole-group update.
        self.update_scan = bool(update_scan)
        if offload_params and not self.amp:
            raise ValueError("offload_params requires strategy.amp (the "
                             "compute copies are bf16)")
        # stream_layers (VERDICT r4 next #7):
        # host-offloaded state is stored PER-LAYER (lists, not one
        # stacked array) and the update python-unrolls over layers
        # behind a depth-``offload_depth`` optimization_barrier chain —
        # layer k+1's host→HBM fetch overlaps layer k's f32 update while
        # layer k−1's new master streams back. With offload_params the
        # forward also runs on PERSISTENT bf16 compute copies carried as
        # trainer state, eliminating the whole-model master fetch+cast
        # the whole-group path pays at the top of every step. Bounded
        # HBM: offload_depth layers' f32 working sets instead of a whole
        # stacked group.
        self.stream_layers = bool(stream_layers)
        # comp_resident (stream_layers + offload_params only): keep the
        # bf16 compute copies as persistent trainer state (fast path —
        # no forward-side master traffic). False streams the forward
        # copies per-layer from the host masters inside the program
        # instead: per-step host traffic grows by one master read, but
        # the program has ~zero HBM *arguments*.
        self.comp_resident = bool(comp_resident)
        # conservative_fetch (stream_layers): additionally gate every
        # host fetch on the layer's GRADIENT, serializing fetches
        # behind backward. Lower peak HBM (no fetch overlaps fwd/bwd)
        # at the cost of the overlap — the knob that fits 1.9B on one
        # v5e, where the free schedule's ~1 GB of early-fetch
        # working set pushes past the 15.75 GB budget (1.3B free 0.4295
        # @ 15.0 GB vs conservative 0.414 @ 4.9 GB in an earlier
        # environment, not reproduced).
        self.conservative_fetch = bool(conservative_fetch)
        if self.stream_layers:
            if not (offload_params or offload_optimizer):
                raise ValueError(
                    "stream_layers requires offload_params and/or "
                    "offload_optimizer (it schedules host streams)")
            if self.v != 1:
                raise ValueError(
                    "stream_layers supports v_virtual == 1 (per-layer "
                    "groups assume the [pp, lps, ...] stacking)")
        # PADDLE_TPU_FAKE_PINNED_HOST=1 (tests only): XLA:CPU has no
        # pinned_host memory space, so the virtual-mesh tests exercise
        # the full streaming program structure with both "spaces"
        # mapped to default device memory — placement differs, math and
        # schedule constraints are identical.
        if os.environ.get("PADDLE_TPU_FAKE_PINNED_HOST") == "1":
            self._host_kind, self._dev_kind = None, None
        else:
            self._host_kind, self._dev_kind = "pinned_host", "device"
        self.unroll_layers = unroll_layers
        # quantized DP-gradient sync (distributed/qcomm.py, ROADMAP 3b):
        # same semantics and constraints as the strategy compiler's knob
        # — per-shard local grads inside an all-manual shard_map over
        # 'dp', reduced by the EQuARX-style compressed ring. Pure-DP
        # only: the pipeline/tp/sp manual regions and ZeRO's grad
        # sharding don't compose with the wrap yet (residue).
        from .qcomm import validate_dp_grad_comm

        validate_dp_grad_comm(
            dp_grad_comm, self.mesh, zero_stage=self.zero,
            block=int(dp_grad_block),
            unsupported=(("offload_params (the host-streamed update "
                          "builders bypass the shard_map grad wrap)",
                          offload_params),
                         ("stream_layers", stream_layers)))
        self.dp_grad_comm = dp_grad_comm
        self.dp_grad_block = int(dp_grad_block)

        self._param_ns = lambda sp: NamedSharding(
            self.mesh, sp, memory_kind=self._host_kind) \
            if self.offload_params else NamedSharding(self.mesh, sp)

        blocks = list(model.pipeline_blocks())
        L = len(blocks)
        if L % (self.pp * self.v) != 0:
            raise ValueError(
                f"{L} blocks must be divisible by pp_degree×v_virtual="
                f"{self.pp}×{self.v}")
        self.lps = L // self.pp
        self.n_layers = L

        # --- split state: block params (stacked) vs the rest --------------
        pn, pt, bn, bt = state_tensors(model)
        name_by_id = {id(t): n for n, t in zip(pn, pt)}
        base_specs = resolve_param_specs(model, self.mesh, zero_stage=0)

        sfx0, t0 = state_tensors(blocks[0])[:2]
        self.block_suffixes = list(sfx0)
        self._blk0_tensors = list(t0)
        self._blk0_fullnames = [name_by_id[id(t)] for t in t0]
        per_block_tensors: List[List[Tensor]] = [t0]
        block_ids = set(id(t) for t in t0)
        for b in blocks[1:]:
            sfx_i, t_i = state_tensors(b)[:2]
            if list(sfx_i) != self.block_suffixes:
                raise ValueError(
                    "pipeline blocks must have identical structure; "
                    f"{sfx_i} != {self.block_suffixes}")
            per_block_tensors.append(list(t_i))
            block_ids.update(id(t) for t in t_i)

        self.other_names = [n for n, t in zip(pn, pt)
                            if id(t) not in block_ids]
        name2t = dict(zip(pn, pt))
        self._name2tensor = name2t
        self._per_block_tensors = per_block_tensors

        # LazyGuard (framework/lazy.py) models: every param is a
        # ShapeDtypeStruct. The trainer then *plans* instead of allocating
        # — stack/cast/shard through jax.eval_shape, optimizer state via
        # eval_shape of _init_state, and step() is AOT-only
        # (lower/compile/memory_analysis). This is the 13B path: planning
        # a 156 GB-state model allocates nothing anywhere.
        from ..framework.lazy import is_abstract
        self.abstract = any(is_abstract(t) for t in pt)

        dp = self.mesh.shape.get("dp", 1)

        # ZeRO-1/2 manual weight-update sharding (ISSUE 19; Xu et al.
        # 2004.13336): on a pure-DP mesh, stages 1-2 run the update
        # inside the ONE dp shard_map — reduce-scatter grads to their
        # owner shard (quantized or f32 ring per dp_grad_comm),
        # optimizer update on only the owned flat slice (state at
        # shard shape: the memory win), all-gather updated params back
        # (dp_param_comm payload). Compositions the manual wrap does
        # not cover yet fall back to the GSPMD _add_axis spelling
        # below (same memory claim, implicit collectives): host
        # offload / layer streaming (their update builders bypass the
        # wrap), storage-dtype casts, update_scan, and abstract
        # (LazyGuard) planning.
        pure_dp = all(s == 1 for a, s in self.mesh.shape.items()
                      if a != "dp")
        self.zero_manual = bool(
            self.zero in (1, 2) and dp > 1 and pure_dp
            and not self.abstract
            and not (offload_optimizer or offload_params
                     or stream_layers or update_scan)
            and self.param_dtype is None and self.moment_dtype is None)
        from . import qcomm as _qcomm
        if dp_param_comm is None:
            dp_param_comm = "bf16" if (self.zero_manual
                                       and dp_grad_comm == "int8") \
                else "f32"
        _qcomm.validate_dp_param_comm(dp_param_comm, self.zero_manual)
        self.dp_param_comm = dp_param_comm
        if self.zero_manual:
            gclip = optimizer._grad_clip
            from ..nn import ClipGradByGlobalNorm
            if gclip is not None and not isinstance(gclip,
                                                    ClipGradByGlobalNorm):
                raise NotImplementedError(
                    "ZeRO sharded update supports grad clipping only "
                    "by global norm (per-leaf clips need the full "
                    "gradient on every shard); got "
                    f"{type(gclip).__name__}")

        # stacked block params: [pp, lps, ...] (GPipe) or
        # [pp, v, lps/v, ...] (interleaved: stage s circuit c owns layers
        # (c·pp + s)·lps_v .. +lps_v — the circular assignment)
        part = _ptrace.phase("setup/trainer/stack_blocks").begin()
        self.block_vals: Dict[str, jax.Array] = {}
        self.block_specs: Dict[str, P] = {}
        # stream_layers: per-layer piece specs [pp, ...] and, with
        # offload_params, persistent bf16 compute copies (trainer state)
        self.block_layer_specs: Dict[str, P] = {}
        self.block_comp: Dict[str, jax.Array] = {}
        self.other_comp: List[jax.Array] = []
        for j, sfx in enumerate(self.block_suffixes):
            base = per_block_tensors[0][j]._value
            if self.v == 1:
                full_shape = (self.pp, self.lps) + tuple(base.shape)
                extra = (None,)
            else:
                lps_v = self.lps // self.v
                full_shape = (self.pp, self.v, lps_v) + tuple(base.shape)
                extra = (None, None)
            spec0 = base_specs[self._blk0_fullnames[j]]
            pp_ax = "pp" if "pp" in self.mesh.axis_names else None
            spec = P(pp_ax, *extra, *spec0)
            if self.zero >= 3:
                shape = _local_check_shape(full_shape, spec, self.mesh)
                spec = _add_axis(spec, len(full_shape), shape, "dp", dp)
            self.block_specs[sfx] = spec
            dt = base.dtype
            if self.param_dtype is not None and \
                    jnp.issubdtype(dt, jnp.floating):
                dt = self.param_dtype
            if self.stream_layers:
                lspec = P(pp_ax, *spec0)
                pshape = (self.pp,) + tuple(base.shape)
                if self.zero >= 3:
                    lshape = _local_check_shape(pshape, lspec, self.mesh)
                    lspec = _add_axis(lspec, len(pshape), lshape, "dp", dp)
                self.block_layer_specs[sfx] = lspec
            if self.stream_layers and self.offload_params:
                # per-layer host masters + one resident bf16 compute
                # stack. The full f32 stack is never materialized on
                # device (at 2.7B it would not fit next to the eager
                # params), and eager buffers are freed suffix-by-suffix
                # so the init peak declines as the comp copies grow.
                fl = jnp.issubdtype(dt, jnp.floating)
                cdt = jnp.bfloat16 if fl else dt
                lns = self._param_ns(self.block_layer_specs[sfx])
                pshape = (self.pp,) + tuple(base.shape)
                if self.abstract:
                    self.block_vals[sfx] = [
                        jax.ShapeDtypeStruct(pshape, dt, sharding=lns)
                        for _ in range(self.lps)]
                    if self.comp_resident:
                        self.block_comp[sfx] = jax.ShapeDtypeStruct(
                            full_shape, cdt,
                            sharding=NamedSharding(self.mesh, spec))
                else:
                    pieces, comp_pieces = [], []
                    for i in range(self.lps):
                        vals = [per_block_tensors[s * self.lps + i][j]
                                ._value for s in range(self.pp)]
                        piece = jnp.stack(vals, 0)
                        if dt != piece.dtype:
                            piece = piece.astype(dt)
                        pieces.append(jax.device_put(piece, lns))
                        if self.comp_resident:
                            comp_pieces.append(piece.astype(cdt))
                    self.block_vals[sfx] = pieces
                    if self.comp_resident:
                        self.block_comp[sfx] = jax.device_put(
                            jnp.stack(comp_pieces, 1),
                            NamedSharding(self.mesh, spec))
                    if free_eager:
                        for i in range(L):
                            t = per_block_tensors[i][j]
                            if t._value is not None:
                                t._value.delete()
                                t._value = None
                continue
            if self.abstract:
                stacked = jax.ShapeDtypeStruct(full_shape, base.dtype)
            else:
                per_layer = [per_block_tensors[i][j]._value
                             for i in range(L)]
                stacked = jnp.stack(per_layer, 0)
                if self.v == 1:
                    stacked = stacked.reshape(full_shape)
                else:
                    stacked = stacked.reshape(
                        (self.v, self.pp, lps_v) + per_layer[0].shape)
                    stacked = jnp.swapaxes(stacked, 0, 1)  # [pp,v,lps_v,...]
            if self.abstract:
                self.block_vals[sfx] = jax.ShapeDtypeStruct(
                    full_shape, dt, sharding=self._param_ns(spec))
            else:
                if dt != stacked.dtype:
                    stacked = stacked.astype(dt)
                self.block_vals[sfx] = jax.device_put(
                    stacked, self._param_ns(spec))
        part.end()

        part = _ptrace.phase("setup/trainer/place_others").begin()
        self.other_vals: List[jax.Array] = []
        self.other_specs: List[P] = []
        for n in self.other_names:
            spec = base_specs[n]
            t = name2t[n]
            if self.zero >= 3:
                shape = _local_check_shape(t._value.shape, spec, self.mesh)
                spec = _add_axis(spec, t._value.ndim, shape, "dp", dp)
            self.other_specs.append(spec)
            v = t._value
            dt = v.dtype
            if self.param_dtype is not None and \
                    jnp.issubdtype(dt, jnp.floating):
                dt = self.param_dtype
            stream_comp = self.stream_layers and self.offload_params \
                and self.comp_resident
            if stream_comp:
                cdt = jnp.bfloat16 if jnp.issubdtype(dt, jnp.floating) \
                    else dt
            if self.abstract:
                self.other_vals.append(jax.ShapeDtypeStruct(
                    tuple(v.shape), dt, sharding=self._param_ns(spec)))
                if stream_comp:
                    self.other_comp.append(jax.ShapeDtypeStruct(
                        tuple(v.shape), cdt,
                        sharding=NamedSharding(self.mesh, spec)))
            else:
                if dt != v.dtype:
                    v = v.astype(dt)
                if stream_comp:
                    self.other_comp.append(jax.device_put(
                        v.astype(cdt), NamedSharding(self.mesh, spec)))
                self.other_vals.append(jax.device_put(
                    v, self._param_ns(spec)))
        part.end()

        # --- optimizer state ----------------------------------------------
        part = _ptrace.phase("setup/trainer/opt_state").begin()
        def opt_state_spec(spec, shape, ndim):
            if self.zero >= 1:
                local = _local_check_shape(shape, spec, self.mesh)
                return _add_axis(spec, ndim, local, "dp", dp)
            return spec

        class _FakeParam:
            def __init__(self, v):
                self._value = v

        def cast_state(s):
            if self.moment_dtype is None:
                return s
            return {k: v.astype(self.moment_dtype)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v
                    for k, v in s.items()}

        self._opt_ns = lambda sp: NamedSharding(
            self.mesh, sp, memory_kind=self._host_kind) \
            if self.offload_optimizer else NamedSharding(self.mesh, sp)

        def init_opt_state(v, sp):
            """Optimizer state for one (stacked) param: real arrays
            normally; shape-only (eval_shape of _init_state) in abstract
            mode, with the moment-dtype cast applied to the metadata."""
            if not self.abstract:
                s = cast_state(optimizer._init_state(_FakeParam(v)))
                return jax.device_put(s, {k: self._opt_ns(sp) for k in s})
            s = jax.eval_shape(
                lambda vv: optimizer._init_state(_FakeParam(vv)),
                jax.ShapeDtypeStruct(v.shape, v.dtype))
            out = {}
            for k, sd in s.items():
                dt = sd.dtype
                if self.moment_dtype is not None and \
                        jnp.issubdtype(dt, jnp.floating):
                    dt = self.moment_dtype
                out[k] = jax.ShapeDtypeStruct(
                    tuple(sd.shape), dt, sharding=self._opt_ns(sp))
            return out

        self.block_opt: Dict[str, dict] = {}
        self.block_opt_specs: Dict[str, dict] = {}
        self.other_opt: List[dict] = []
        self.other_opt_specs: List[dict] = []
        if self.zero_manual:
            # ONE fused flat slab per optimizer-state key, dp-sharded
            # [dp*chunk] (plus the f32 master param copy when the param
            # all-gather is compressed — bf16 round-trip rounding would
            # swallow small updates without it). It rides the regular
            # block_opt plumbing under a sentinel suffix so device
            # state / checkpointing / donation stay structure-agnostic.
            leaves = jax.tree_util.tree_leaves(
                (self.block_vals, self.other_vals))
            sizes = [int(np.prod(v.shape)) for v in leaves]
            self._zero_sizes = sizes
            self._zero_chunk = _qcomm.zero_chunk_len(
                sum(sizes), dp, self.dp_grad_block)
            slab = dp * self._zero_chunk
            st = optimizer._init_state(
                _FakeParam(jnp.zeros((slab,), jnp.float32)))
            if self.dp_param_comm != "f32":
                flat = np.concatenate(
                    [np.asarray(v, np.float32).reshape(-1)
                     for v in leaves]) if leaves \
                    else np.zeros(0, np.float32)
                st["master"] = jnp.asarray(
                    np.pad(flat, (0, slab - flat.size)))
            dp_sh = NamedSharding(self.mesh, P("dp"))
            self.block_opt[_ZERO_SLAB] = {
                k: jax.device_put(v, dp_sh) for k, v in st.items()}
            self.block_opt_specs[_ZERO_SLAB] = {k: P("dp") for k in st}
        for sfx, v in (() if self.zero_manual
                       else self.block_vals.items()):
            if self.stream_layers and self.offload_optimizer:
                # per-layer host-resident optimizer state (lists of
                # dicts, parallel to the per-layer masters)
                if isinstance(v, list):
                    pav = jax.ShapeDtypeStruct(tuple(v[0].shape),
                                               v[0].dtype)
                else:
                    pav = jax.ShapeDtypeStruct(
                        (v.shape[0],) + tuple(v.shape[2:]), v.dtype)
                sp = opt_state_spec(self.block_layer_specs[sfx],
                                    pav.shape, len(pav.shape))
                lst = [init_opt_state(pav, sp) for _ in range(self.lps)]
                self.block_opt[sfx] = lst
                self.block_opt_specs[sfx] = {k: sp for k in lst[0]}
                continue
            if isinstance(v, list):
                # stream_layers+offload_params with RESIDENT moments:
                # stacked state from the stacked master aval
                # (_init_state is shape-only; no f32 stack materializes)
                v = jax.ShapeDtypeStruct(
                    (self.pp, self.lps) + tuple(v[0].shape[1:]),
                    v[0].dtype)
            sp = opt_state_spec(self.block_specs[sfx], v.shape, v.ndim)
            s = init_opt_state(v, sp)
            self.block_opt[sfx] = s
            self.block_opt_specs[sfx] = {k: sp for k in s}
        for n, v, spec in (() if self.zero_manual else zip(
                self.other_names, self.other_vals, self.other_specs)):
            sp = opt_state_spec(spec, v.shape, v.ndim)
            s = init_opt_state(v, sp)
            self.other_opt.append(s)
            self.other_opt_specs.append({k: sp for k in s})
        part.end()

        if free_eager and not self.abstract:
            part = _ptrace.phase("setup/trainer/free_eager").begin()
            # device_put may return a NEW Array sharing the SAME buffer
            # when dtype+sharding are unchanged, so aliasing cannot be
            # detected by identity. Delete only buffers that are
            # provably fresh copies: per-layer block params (jnp.stack
            # always materializes a new stacked buffer) and other params
            # whose dtype cast forced a copy. An uncast "other" param
            # keeps sharing its buffer with the trainer — dropping the
            # eager reference alone still releases nothing extra, and
            # deleting would kill the trainer's own state.
            for ts in per_block_tensors:
                for t in ts:
                    if t._value is not None:   # stream path freed it
                        t._value.delete()
                        t._value = None
            for n, v in zip(self.other_names, self.other_vals):
                t = name2t[n]
                if t._value.dtype != v.dtype:
                    t._value.delete()
                t._value = None
            part.end()

        self.guard_bad_steps = bool(guard_bad_steps)
        if self.guard_bad_steps and (offload_params or stream_layers):
            raise ValueError(
                "guard_bad_steps is not supported with offload_params/"
                "stream_layers yet (the bad-step select would stream "
                "host-resident state through HBM a second time); "
                "offload_optimizer alone composes — its deselect runs "
                "on the device copies already fetched for the update")
        # device-side verdict of the last guarded step (None before the
        # first step / when unguarded); _fault_scale poisons exactly one
        # upcoming step's loss (chaos harness hook)
        self._last_ok_dev = None
        self._fault_scale: Optional[float] = None

        self._step = 0
        self._n_batch_args: Optional[int] = None
        self._step_fn = None
        #: the blocks' ``aux_stats`` of the last step, summed over its
        #: layers and micro-batches (device arrays; {} for a dense model)
        self.aux_stats = {}

    # ---------------------------------------------------------------------
    def _forward_loss(self, block_params, other_params, batch, key):
        """``(loss, stats)``: the scalar to differentiate, the blocks'
        weighted auxiliary losses added, and their counts summed over
        layers and micro-batches ({} for a dense model)."""
        model = self.model
        from ..core import rng as rng_mod

        if self.amp:
            castf = lambda v: v.astype(jnp.bfloat16) if \
                jnp.issubdtype(v.dtype, jnp.floating) else v
        else:
            castf = lambda v: v
        other_cast = [castf(v) for v in other_params]
        block_cast = {k: castf(v) for k, v in block_params.items()}

        other_tensors = [self._name2tensor[n] for n in self.other_names]
        blk0_tensors = self._blk0_tensors
        sp = self.mesh.shape.get("sp", 1)

        def seq_constraint(h):
            """Keep activations sequence-sharded between ring attentions.
            Skipped for bf16 on XLA:CPU (tests): resharding constraints on
            bf16 trip a CPU-backend crash; TPU is unaffected."""
            if sp > 1 and not (_target_platform() == "cpu"
                               and h.dtype == jnp.bfloat16):
                return jax.lax.with_sharding_constraint(
                    h, NamedSharding(self.mesh, P("dp", "sp", None)))
            return h

        from . import context as dctx
        manual_sp = sp > 1 and self.pp > 1
        block0 = model.pipeline_blocks()[0]

        moe = self.moe

        def one_block(h, layer_params):
            vals = [layer_params[s] for s in self.block_suffixes]
            # kernel_scope: Pallas kernels nest a shard_map over
            # whatever mesh axes are still GSPMD-auto here — all of
            # them at pp == 1 in a plain jit, the non-manual ones
            # inside the pipeline's region, none inside qcomm's
            # all-manual dp wrap (XLA does not partition a Mosaic
            # call)
            with _swapped_state(blk0_tensors, vals), \
                    dctx.kernel_scope(self.mesh):
                if manual_sp:
                    with dctx.manual_sequence_parallel_scope():
                        out = block0(Tensor(h))._value
                else:
                    out = block0(Tensor(h))._value
                aux = {"loss": block0.aux_loss._value,
                       **{k: v._value for k, v in
                          block0.aux_stats.items()}} if moe else None
            return (out, aux) if moe else out

        # recomputation wraps the block of ONE micro-batch on every mesh,
        # never a loop over them: what is saved between forward and
        # backward is x of every (layer, micro-batch) in both loop orders
        if self.remat:
            if self.remat_policy == "dots":
                one_block = jax.checkpoint(
                    one_block,
                    policy=jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable)
            else:
                one_block = jax.checkpoint(one_block)

        def layer_scan(apply_layer, x, stage_local):
            """``lax.scan`` of ``apply_layer(x, layer_params)`` over the
            stacked layers; MoE models: also the blocks' auxiliary values
            (float32), stacked ``[layers, ...]``."""
            def body(h, layer_params):
                out = apply_layer(h, layer_params)
                return out if moe else (out, None)

            unroll = self.unroll_layers if self.unroll_layers is not None \
                else (_target_platform() != "cpu" and not self.remat)
            out, auxs = jax.lax.scan(body, x, stage_local, unroll=unroll)
            return out, jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), auxs)

        def block_apply(stage_local, x):
            """pp > 1, the pipeline's ``stage_fn``: one stage's lps blocks
            on one micro-batch (the micro-batch loop is the schedule's
            clock, outside). MoE models: returns (out, sums over the
            stage's blocks of their weighted auxiliary loss and of their
            counts) — the pipeline's stage_aux contract."""
            out, auxs = layer_scan(one_block, x, stage_local)
            if moe:
                return out, jax.tree_util.tree_map(
                    lambda a: jnp.sum(a, axis=0), auxs)
            return out

        def split_aux(aux):
            # pipeline_apply's carry holds means over the micro-batches:
            # right for the loss; the counts are sums
            stats = {k: v * self.n_micro for k, v in aux.items()
                     if k != "loss"}
            return aux["loss"], stats

        def blocks_one_stage(stacked, x):
            """pp == 1: every block on every micro-batch, layers outside
            and micro-batches inside (the module docstring says why): the
            scan's body gets one layer's parameters and maps the block
            over ``x`` as ``[n_micro, micro, seq, h]``. Returns the
            activations and, MoE models, (mean over micro-batches of the
            blocks' summed auxiliary loss, their counts summed over
            layers and micro-batches)."""
            # [1, L, ...] or, interleaved, [1, v, L/v, ...]: the chunks'
            # layers in order
            layers = jax.tree_util.tree_map(
                lambda a: a[0].reshape((-1,) + tuple(a.shape[3:]))
                if self.v > 1 else a[0], stacked)
            mbs, auxs = layer_scan(
                lambda hs, layer_params: jax.lax.map(
                    lambda h: one_block(h, layer_params), hs),
                _to_microbatches(x, self.n_micro), layers)
            out = _from_microbatches(mbs, x.shape)
            if not moe:
                return out, None, {}
            # [L, n_micro] a leaf: over the layers first, as a stage's
            # aux is summed under pp > 1
            sums = jax.tree_util.tree_map(
                lambda a: jnp.sum(jnp.sum(a, axis=0), axis=0), auxs)
            return out, sums.pop("loss") / self.n_micro, sums

        batch_tensors = [Tensor(b) for b in batch]
        # loss-inside-pipeline: the head runs in the manual region, the
        # stages sharing it: the last stage deals its finished
        # micro-batches out over 'pp' (1/pp of the activation buffer to
        # each other stage, their dx back in the backward pass) and each
        # runs the head on its own against its rows of the batch; sums
        # and counts of the loss's terms cross 'pp' back. Where n_micro
        # is not a multiple of pp every stage runs the whole head and the
        # last one's value is kept (pipeline.py "Loss egress"). Disabled
        # under manual sp (head must see the sp-sharded output) and under
        # CPU+amp (bf16 cotangent psum trips XLA:CPU). tp>1 is supported:
        # the vocab-sharded head's tp collectives ride GSPMD-auto inside
        # the manual-pp region like the blocks' do.
        head_inside = not manual_sp and self.pp > 1 and not (
            _target_platform() == "cpu" and self.amp) and \
            os.environ.get("PADDLE_TPU_HEAD_INSIDE", "1") != "0"
        with _swapped_state(other_tensors, other_cast), \
                dctx.sequence_parallel_scope(self.mesh):
            with rng_mod.key_scope(key):
                # fwd/* named scopes: pure op-name metadata traced into
                # the program, so XLA traces/HLO dumps attribute device
                # time to the phase (profiler/trace.py annotate)
                with _ptrace.annotate("fwd/stem"):
                    x = model.pipeline_stem(*batch_tensors)._value
                    x = seq_constraint(x)
                if head_inside:
                    # head params + batch enter the manual region as
                    # explicit inputs; blocks' swapped values are local
                    def head_fn(rows, other_vals, *batch_rows):
                        # (mean, count) terms where the model gives
                        # them: a share's count of kept labels may differ
                        head = getattr(model, "pipeline_head_terms",
                                       model.pipeline_head)
                        with _swapped_state(other_tensors,
                                            list(other_vals)), \
                                _ptrace.annotate("fwd/head"):
                            out = head(Tensor(rows),
                                       *[Tensor(b) for b in batch_rows])
                        if isinstance(out, Tensor):
                            return out._value
                        return tuple((m._value, getattr(c, "_value", c))
                                     for m, c in out)
                    with _ptrace.annotate("fwd/blocks"):
                        loss_v = pipeline_apply(
                            self.mesh, block_apply, block_cast, x,
                            self.n_micro, v_virtual=self.v,
                            head_fn=head_fn,
                            head_args=(tuple(other_cast),),
                            head_batch=tuple(batch), stage_aux=moe)
                    if moe:
                        loss_v, aux = loss_v
                        aux, stats = split_aux(aux)
                        return (loss_v + aux).astype(jnp.float32), stats
                    return loss_v.astype(jnp.float32), {}
                with _ptrace.annotate("fwd/blocks"):
                    if self.pp == 1:
                        x, aux, stats = blocks_one_stage(block_cast, x)
                    else:
                        x = pipeline_apply(
                            self.mesh, block_apply, block_cast, x,
                            self.n_micro, v_virtual=self.v,
                            sp_axis="sp" if manual_sp else None,
                            stage_aux=moe)
                        aux, stats = None, {}
                        if moe:
                            x, aux = x
                            aux, stats = split_aux(aux)
                with _ptrace.annotate("fwd/head"):
                    x = Tensor(seq_constraint(x))
                    loss = model.pipeline_head(x, *batch_tensors)
                    if aux is not None:
                        loss = loss + Tensor(aux)
        return loss._value.astype(jnp.float32), stats

    def _cast_back(self, np_, ns, store_p_dtype, store_s):
        """Shared storage-dtype rule for both update builders: the f32
        update result is stored back at the configured param/moment
        dtypes (param_dtype/moment_dtype knobs)."""
        if self.param_dtype is not None and \
                jnp.issubdtype(store_p_dtype, jnp.floating):
            np_ = np_.astype(store_p_dtype)
        if self.moment_dtype is not None:
            ns = {k: v.astype(store_s[k].dtype)
                  if jnp.issubdtype(v.dtype, jnp.floating) else v
                  for k, v in ns.items()}
        return np_, ns

    def _make_batch_spec(self):
        """Batch-arg sharding: dim 0 over dp, dim 1 over sp when present
        (shared by both step builders)."""
        sp = self.mesh.shape.get("sp", 1)

        def batch_spec(ndim):
            if ndim >= 2 and sp > 1:
                return P("dp", "sp")
            return P("dp") if ndim >= 1 else P()

        return batch_spec

    def _update_ctx(self):
        """Shared update-builder prologue: the per-parameter update fn,
        clip, and the per-suffix/per-other lr & decoupled-wd tables
        (used identically by _build and _build_stream)."""
        from .strategy_compiler import make_param_update

        opt = self.optimizer
        wd_other = tuple(opt._decoupled_wd(self._name2tensor[n])
                         for n in self.other_names)
        lr_other = tuple(
            self._name2tensor[n].optimize_attr.get("learning_rate", 1.0)
            for n in self.other_names)
        wd_block = {s: opt._decoupled_wd(t) for s, t in
                    zip(self.block_suffixes, self._blk0_tensors)}
        lr_block = {s: t.optimize_attr.get("learning_rate", 1.0)
                    for s, t in zip(self.block_suffixes,
                                    self._blk0_tensors)}
        return (make_param_update(opt), opt._grad_clip, wd_other,
                lr_other, wd_block, lr_block)

    def _build(self, n_batch_args: int):
        if self.stream_layers:
            return self._build_stream(n_batch_args)
        from .strategy_compiler import functional_clip

        upd, clip, wd_other, lr_other, wd_block, lr_block = \
            self._update_ctx()
        mesh = self.mesh

        offload = self.offload_optimizer
        mesh_ = self.mesh

        def fetch_state(s, spec):
            """Offload: stream host-resident state into HBM for the
            update (XLA inserts the copies; overlappable by the
            latency-hiding scheduler)."""
            if not offload:
                return s
            return {k: jax.device_put(
                v, NamedSharding(mesh_, spec[k],
                                 memory_kind=self._dev_kind))
                for k, v in s.items()}

        offload_p = self.offload_params

        # update_scan (opt-in): the f32 update math materializes f32
        # copies of a WHOLE stacked group (p, g, m, v — at 2.7B the
        # largest group is 0.84 B params ⇒ ~13 GB of f32 transients,
        # which cannot fit next to the resident bf16 state). Scanning
        # the update over the stacked layer dim bounds the f32 working
        # set to ONE layer; the math is elementwise per parameter so the
        # scan is exact.
        scan_update = self.update_scan

        def core_upd(p, g, s_dev, lr, step_no, plr, wd, store_p_dtype,
                     store_s):
            np_, ns = upd(p, g, s_dev, lr, step_no, plr=plr, wd=wd)
            return self._cast_back(np_, ns, store_p_dtype, store_s)

        def upd2(p, g, s, spec, lr, step_no, plr, wd, pspec=None,
                 stacked=False, ok=None):
            """Update in f32 math, store back at the configured dtypes
            (+ host placement handled by out_shardings when offloading).

            ``ok`` (guard_bad_steps): the step verdict. The bad-step
            deselect happens HERE, on the device-resident operands — the
            pre-update param ``p`` and the fetched ``s_dev`` — not on the
            host-resident inputs, so an offloaded optimizer state is
            never streamed through HBM a second time just to undo the
            update: the selected (old) values flow back to pinned_host
            through the same out_shardings the updated ones would."""
            if offload_p:
                p = jax.device_put(p, NamedSharding(
                    mesh_, pspec, memory_kind=self._dev_kind))
            s_dev = fetch_state(s, spec)

            def deselect(np_, ns):
                if ok is None:
                    return np_, ns
                np_ = jnp.where(ok, np_, p)
                ns = {k: jnp.where(ok, v, s_dev[k])
                      for k, v in ns.items()}
                return np_, ns

            if scan_update and stacked and p.ndim >= 3:
                lead = p.shape[0] * p.shape[1]
                pf = p.reshape((lead,) + p.shape[2:])
                gf = g.reshape((lead,) + g.shape[2:])
                sf = {k: v.reshape((lead,) + v.shape[2:])
                      for k, v in s_dev.items()}

                def body(carry, xs):
                    pi, gi, si = xs
                    npi, nsi = core_upd(pi, gi, si, lr, step_no, plr, wd,
                                        p.dtype, {k: s[k] for k in si})
                    return carry, (npi, nsi)

                _, (npf, nsf) = jax.lax.scan(body, 0, (pf, gf, sf))
                np_ = npf.reshape(p.shape)
                ns = {k: v.reshape(s_dev[k].shape)
                      for k, v in nsf.items()}
                return deselect(np_, ns)
            return deselect(
                *core_upd(p, g, s_dev, lr, step_no, plr, wd, p.dtype, s))

        guard = self.guard_bad_steps
        qcomm_dp = self.mesh.shape.get("dp", 1) \
            if self.dp_grad_comm == "int8" else 1
        zero_manual = self.zero_manual
        if zero_manual:
            from .strategy_compiler import _flat_knob, make_flat_update

            zdp = self.mesh.shape.get("dp", 1)
            flat_upd = make_flat_update(self.optimizer)
            clip_norm = float(clip.clip_norm) if clip is not None \
                else None
            slab = zdp * self._zero_chunk
            # knob vectors laid out like the fused param buffer: leaf
            # order is tree_flatten((block_vals, other_vals)) — sorted
            # block suffixes (jax dict order), then the other list
            bkeys = sorted(self.block_vals.keys())
            plr_knob = _flat_knob(
                [lr_block[s] for s in bkeys] + list(lr_other),
                self._zero_sizes, slab)
            wd_knob = _flat_knob(
                [wd_block[s] for s in bkeys] + list(wd_other),
                self._zero_sizes, slab)

        def step_fn(block_params, other_params, block_opt, other_opt,
                    batch, lr, step_no, key, *guard_args):
            # python side effect at the top of the traced body: runs once
            # per trace, so every cache miss (silent recompile) is logged
            # with the batch shapes that triggered it
            _precomp.mark_trace(self._prof_site, batch)
            fault = guard_args[0] if guard else None
            if offload_p:
                # stream masters to HBM and cast; grads flow to the bf16
                # compute copies (half the grad HBM of the f32 path)
                def dev_cast(v, spec):
                    v = jax.device_put(v, NamedSharding(
                        mesh_, spec, memory_kind=self._dev_kind))
                    return v.astype(jnp.bfloat16) \
                        if jnp.issubdtype(v.dtype, jnp.floating) else v
                bp_c = {k: dev_cast(v, self.block_specs[k])
                        for k, v in block_params.items()}
                op_c = [dev_cast(v, s) for v, s in
                        zip(other_params, self.other_specs)]
            else:
                bp_c, op_c = block_params, other_params

            def grads_of(bp, op, batch_, key_, fault_):
                def loss_of(bp_, op_):
                    l, stats = self._forward_loss(bp_, op_, batch_, key_)
                    # fault is 1.0 in normal operation (exact IEEE
                    # noop); the chaos harness sets it to NaN for one
                    # step, which poisons the loss AND (through the
                    # cotangent) every gradient leaf — the guard below
                    # must catch all of it
                    return (l * fault_ if guard else l), stats

                (loss, stats), grads = jax.value_and_grad(
                    loss_of, argnums=(0, 1), has_aux=True)(bp, op)
                return loss, stats, grads

            if zero_manual:
                # ZeRO-1/2 sharded update: the ONE shared shard_map
                # wrap (qcomm.dp_zero_step) does per-shard local
                # grads, fused reduce-scatter (quantized or f32 ring
                # per dp_grad_comm), global-norm clip on the reduced
                # chunks, the guard verdict on the REDUCED shard grads
                # (pmin-agreed across the mesh — every shard takes the
                # identical keep/skip branch), the shard-local flat
                # optimizer update, and the param all-gather
                # (dp_param_comm payload). Replaces the per-suffix
                # upd2 loop below entirely.
                from . import qcomm as _zq

                def local(rep, params_, key_, batch_):
                    bp, op = params_
                    return grads_of(bp, op, batch_, key_, rep)

                ft = fault if guard else jnp.float32(1.0)
                res = _zq.dp_zero_step(
                    mesh, zdp, self.dp_grad_block, self.dp_grad_comm,
                    self.dp_param_comm, local, flat_upd, ft,
                    (block_params, other_params),
                    block_opt[_ZERO_SLAB], batch,
                    _zq.dp_batch_specs(batch, zdp), key, lr, step_no,
                    plr_knob, wd_knob, clip_norm=clip_norm,
                    guard=guard)
                if guard:
                    loss, stats, (nb, no), new_flat, ok = res
                    return (loss, ok, nb, no, {_ZERO_SLAB: new_flat},
                            [], stats)
                loss, stats, (nb, no), new_flat = res
                return loss, nb, no, {_ZERO_SLAB: new_flat}, [], stats

            if qcomm_dp > 1:
                # quantized DP-grad sync: per-shard local grads inside
                # the ONE shared all-manual shard_map wrap (qcomm.py),
                # reduced by the EQuARX-style compressed ring. pmean of
                # the per-shard mean losses == the global mean loss;
                # the quantized ring replaces the grads' pmean — the
                # only numeric difference vs the GSPMD path.
                from . import qcomm as _qcomm

                def local(rep, key_, batch_):
                    bp, op, ft = rep
                    return grads_of(bp, op, batch_, key_, ft)

                ft = fault if guard else jnp.float32(1.0)
                loss, stats, (g_blk, g_oth) = \
                    _qcomm.dp_quantized_value_and_grads(
                        mesh, qcomm_dp, self.dp_grad_block, local,
                        (bp_c, op_c, ft), batch,
                        _qcomm.dp_batch_specs(batch, qcomm_dp), key)
            else:
                loss, stats, (g_blk, g_oth) = grads_of(
                    bp_c, op_c, batch, key, fault)
            with _ptrace.annotate("opt/update"):     # the optimizer's clip
                g_blk, g_oth = functional_clip(clip, (g_blk, g_oth))

            ok = None
            if guard:
                # one scalar verdict for the whole step: loss and every
                # clipped grad leaf finite. isfinite-per-leaf (not a
                # squared global norm) so legitimately-huge-but-finite
                # grads cannot overflow the check itself. The deselect
                # itself happens inside upd2 on device-resident values
                # (see its docstring) — params AND optimizer state stay
                # bit-identical on a bad step (momentum does not decay,
                # weight decay does not apply).
                ok = jnp.isfinite(loss)
                for g_ in jax.tree_util.tree_leaves((g_blk, g_oth)):
                    ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g_)))

            # offload_params: serialize the per-group host↔HBM update
            # streams (fetch k waits on update k-depth) — unconstrained,
            # the scheduler launches every group's copy-in during
            # backward and the transient f32 state OOMs; chained,
            # offload_depth groups' f32 (p, m, v) are in HBM at a time
            # and copy-in of group k overlaps update k-1 and copy-out of
            # group k-depth on the full-duplex link.
            chain = [loss] * self.offload_depth
            any_offload = offload_p or offload

            def barriered(p, g, s):
                # serialize per-group host fetches whenever ANY state is
                # host-resident — with only the optimizer offloaded the
                # unconstrained scheduler would fetch every group's
                # moments during backward and OOM on the f32 update
                # transients (hit at 2.7B moment-offload)
                if not any_offload:
                    return p, g, s
                (p, g, _), s = jax.lax.optimization_barrier(
                    ((p, g, chain.pop(0)), s))
                return p, g, s

            new_blk, new_blk_opt = {}, {}
            new_oth, new_oth_opt = [], []
            # opt/update: scope name of the optimizer update, beside the
            # forward's fwd/* (read by train.opt_ms_per_step)
            with _ptrace.annotate("opt/update"):
                for sfx in block_params:
                    p, g, s = barriered(block_params[sfx], g_blk[sfx],
                                        block_opt[sfx])
                    np_, ns = upd2(p, g, s, self.block_opt_specs[sfx],
                                   lr, step_no, lr_block[sfx],
                                   wd_block[sfx],
                                   pspec=self.block_specs[sfx],
                                   stacked=True, ok=ok)
                    new_blk[sfx] = np_
                    new_blk_opt[sfx] = ns
                    if any_offload:
                        chain.append(np_)
                for p, g, s, sspec, pspec, plr, wd in zip(
                        other_params, g_oth, other_opt,
                        self.other_opt_specs, self.other_specs, lr_other,
                        wd_other):
                    p, g, s = barriered(p, g, s)
                    np_, ns = upd2(p, g, s, sspec, lr, step_no, plr, wd,
                                   pspec=pspec, ok=ok)
                    new_oth.append(np_)
                    new_oth_opt.append(ns)
                    if any_offload:
                        chain.append(np_)
            if guard:
                return (loss, ok, new_blk, new_oth, new_blk_opt,
                        new_oth_opt, stats)
            return loss, new_blk, new_oth, new_blk_opt, new_oth_opt, stats

        ns = lambda spec: NamedSharding(mesh, spec)
        ons = self._opt_ns          # pinned_host when offloading
        pns = self._param_ns        # pinned_host when offload_params
        blk_sh = {k: pns(v) for k, v in self.block_specs.items()}
        oth_sh = [pns(s) for s in self.other_specs]
        blk_opt_sh = {k: {kk: ons(vv) for kk, vv in v.items()}
                      for k, v in self.block_opt_specs.items()}
        oth_opt_sh = [{kk: ons(vv) for kk, vv in d.items()}
                      for d in self.other_opt_specs]
        self._batch_spec = self._make_batch_spec()
        in_sh = (blk_sh, oth_sh, blk_opt_sh, oth_opt_sh,
                 None, None, None, None)
        out_sh = (ns(P()), blk_sh, oth_sh, blk_opt_sh, oth_opt_sh,
                  ns(P()))                                # + aux_stats
        if guard:
            in_sh = in_sh + (None,)                       # fault scalar
            out_sh = (ns(P()), ns(P())) + out_sh[1:]      # + ok verdict
        self._step_fn = jax.jit(
            step_fn, in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=(0, 1, 2, 3))
        self._n_batch_args = n_batch_args
        self._first_call_due = True

    def _build_stream(self, n_batch_args: int):
        """stream_layers step: per-layer host↔HBM streaming update.

        One pjit program; ordering comes from a depth-``offload_depth``
        optimization_barrier chain seeded on the step counter, so the
        first ``depth`` layer fetches launch at program start and hide
        under forward/backward, after which fetch k waits on update
        k−depth (not on its writeback):

            fetch layer k+1 (host→HBM) ∥ f32 update layer k ∥
            writeback layer k−1 (HBM→host)

        With offload_params the forward/backward run on PERSISTENT bf16
        compute copies carried as trainer state and rebuilt by each
        update, so per-step host traffic is exactly one master read +
        one master write — the whole-group path's additional whole-
        model master fetch+cast at the top of every step is gone.
        Reference analogue: the staged ZeRO-Offload update
        (reference: python/paddle/incubate/optimizer/distributed_fused_lamb.py,
        paddle/fluid/operators/optimizers/distributed_fused_lamb_op.cc),
        scheduled here by XLA instead of CUDA streams."""
        from .strategy_compiler import functional_clip

        upd, clip, wd_other, lr_other, wd_block, lr_block = \
            self._update_ctx()
        mesh = self.mesh
        offload_p = self.offload_params
        offload_o = self.offload_optimizer
        depth = self.offload_depth
        devk = self._dev_kind
        lps = self.lps
        sfx_list = list(self.block_suffixes)

        def to_dev(v, spec):
            return jax.device_put(
                v, NamedSharding(mesh, spec, memory_kind=devk))

        def bf16_of(v):
            return v.astype(jnp.bfloat16) \
                if jnp.issubdtype(v.dtype, jnp.floating) else v

        def one_group(pm, g, s, gate, p_spec, s_specs, plr, wd, lr,
                      step_no):
            """Barrier-gated fetch → f32 update → storage-dtype cast for
            one parameter group (one layer's suffix, or one 'other').

            By default only the HOST-RESIDENT operands (pm, s) are tied
            to the gate: including g would chain the fetch to the
            gradient, which the layer-scan backward produces only at
            its end — serializing every fetch behind backward (the r4
            behavior this rework removes). g is device-resident and
            needs no gating; the update itself waits on it naturally.
            conservative_fetch opts back into the grad gate where the
            free schedule's early-fetch working set exceeds HBM."""
            if self.conservative_fetch:
                (pm, g, _), s = jax.lax.optimization_barrier(
                    ((pm, g, gate), s))
            else:
                (pm, _), s = jax.lax.optimization_barrier(
                    ((pm, gate), s))
            pm_d = to_dev(pm, p_spec) if offload_p and p_spec is not None \
                else pm
            s_d = {k: to_dev(v, s_specs[k]) for k, v in s.items()} \
                if offload_o and s_specs is not None else s
            np_, ns = upd(pm_d, g, s_d, lr, step_no, plr=plr, wd=wd)
            return self._cast_back(np_, ns, pm.dtype, s)

        comp_res = self.comp_resident

        def step_fn(blk_m, oth_m, blk_c, oth_c, blk_o, oth_o,
                    batch, lr, step_no, key):
            _precomp.mark_trace(self._prof_site, batch)
            if offload_p and not comp_res:
                # no persistent compute copies: stream the forward's
                # bf16 copies per-layer from the host masters, chained
                # so ≤depth f32 pieces are in flight (the zero-argument
                # layout — see comp_resident in __init__)
                fchain = [step_no] * depth
                bl = {s: [None] * lps for s in sfx_list}
                for i in range(lps):
                    gate = fchain.pop(0)
                    last = gate
                    for sfx in sfx_list:
                        (pm, _) = jax.lax.optimization_barrier(
                            (blk_m[sfx][i], gate))
                        c = bf16_of(to_dev(
                            pm, self.block_layer_specs[sfx]))
                        bl[sfx][i] = c
                        last = c
                    fchain.append(last)
                bp = {s: jax.lax.with_sharding_constraint(
                    jnp.stack(bl[s], 1),
                    NamedSharding(mesh, self.block_specs[s]))
                    for s in sfx_list}
                op = [bf16_of(to_dev(oth_m[idx], self.other_specs[idx]))
                      for idx in range(len(oth_m))]
            elif offload_p:
                bp, op = blk_c, oth_c
            else:
                bp, op = blk_m, oth_m

            def loss_of(b, o):
                return self._forward_loss(b, o, batch, key)

            (loss, stats), (g_blk, g_oth) = jax.value_and_grad(
                loss_of, argnums=(0, 1), has_aux=True)(bp, op)
            g_blk, g_oth = functional_clip(clip, (g_blk, g_oth))

            chain = [step_no] * depth
            new_m = {s: [None] * lps for s in sfx_list}
            new_c = {s: [None] * lps for s in sfx_list}
            new_o = {s: [None] * lps for s in sfx_list}
            for i in range(lps):
                gate = chain.pop(0)
                last = gate
                for sfx in sfx_list:
                    if offload_p:
                        pm = blk_m[sfx][i]
                    else:
                        pm = jax.lax.index_in_dim(blk_m[sfx], i, 1,
                                                  keepdims=False)
                    g = jax.lax.index_in_dim(g_blk[sfx], i, 1,
                                             keepdims=False)
                    if offload_o:
                        s = blk_o[sfx][i]
                    else:
                        s = {k: jax.lax.index_in_dim(v, i, 1,
                                                     keepdims=False)
                             for k, v in blk_o[sfx].items()}
                    np_, ns = one_group(
                        pm, g, s, gate, self.block_layer_specs[sfx],
                        self.block_opt_specs[sfx] if offload_o else None,
                        lr_block[sfx], wd_block[sfx], lr, step_no)
                    new_m[sfx][i] = np_
                    if offload_p and comp_res:
                        new_c[sfx][i] = bf16_of(np_)
                    new_o[sfx][i] = ns
                    last = np_
                chain.append(last)

            new_oth_m, new_oth_c, new_oth_o = [], [], []
            for idx in range(len(oth_m)):
                gate = chain.pop(0)
                np_, ns = one_group(
                    oth_m[idx], g_oth[idx], oth_o[idx], gate,
                    self.other_specs[idx],
                    self.other_opt_specs[idx] if offload_o else None,
                    lr_other[idx], wd_other[idx], lr, step_no)
                new_oth_m.append(np_)
                if offload_p and comp_res:
                    new_oth_c.append(bf16_of(np_))
                new_oth_o.append(ns)
                chain.append(np_)

            if offload_p:
                out_blk_m = new_m
                out_blk_c = {s: jnp.stack(new_c[s], 1)
                             for s in sfx_list} if comp_res else {}
            else:
                out_blk_m = {s: jnp.stack(new_m[s], 1) for s in sfx_list}
                out_blk_c = {}
            if offload_o:
                out_blk_o = new_o
            else:
                out_blk_o = {s: {k: jnp.stack(
                    [new_o[s][i][k] for i in range(lps)], 1)
                    for k in blk_o[s]} for s in sfx_list}
            return (loss, out_blk_m, new_oth_m, out_blk_c, new_oth_c,
                    out_blk_o, new_oth_o, stats)

        ns = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
        pns = self._param_ns
        ons = self._opt_ns
        if offload_p:
            blk_m_sh = {s: [pns(self.block_layer_specs[s])] * lps
                        for s in sfx_list}
            if comp_res:
                blk_c_sh = {s: ns(self.block_specs[s])
                            for s in sfx_list}
                oth_c_sh = [ns(sp) for sp in self.other_specs]
            else:
                blk_c_sh, oth_c_sh = {}, []
        else:
            blk_m_sh = {s: pns(self.block_specs[s]) for s in sfx_list}
            blk_c_sh, oth_c_sh = {}, []
        oth_m_sh = [pns(sp) for sp in self.other_specs]
        if offload_o:
            blk_o_sh = {s: [{k: ons(v) for k, v in
                             self.block_opt_specs[s].items()}] * lps
                        for s in sfx_list}
        else:
            blk_o_sh = {s: {k: ons(v) for k, v in
                            self.block_opt_specs[s].items()}
                        for s in sfx_list}
        oth_o_sh = [{k: ons(v) for k, v in d.items()}
                    for d in self.other_opt_specs]
        self._batch_spec = self._make_batch_spec()
        self._step_fn = jax.jit(
            step_fn,
            in_shardings=(blk_m_sh, oth_m_sh, blk_c_sh, oth_c_sh,
                          blk_o_sh, oth_o_sh, None, None, None, None),
            out_shardings=(ns(P()), blk_m_sh, oth_m_sh, blk_c_sh,
                           oth_c_sh, blk_o_sh, oth_o_sh, ns(P())),
            donate_argnums=(0, 1, 2, 3, 4, 5))
        self._n_batch_args = n_batch_args
        self._first_call_due = True

    def _state_args(self):
        if self.stream_layers:
            return (self.block_vals, self.other_vals, self.block_comp,
                    self.other_comp, self.block_opt, self.other_opt)
        return (self.block_vals, self.other_vals, self.block_opt,
                self.other_opt)

    def _first_call(self, *args):
        """The step program's first call, phase ``setup/first_call``
        [``site``]: it is traced, lowered and compiled or fetched from
        the cache in here, and recompile.py's listener charges those
        seconds to the site."""
        with _ptrace.phase(_precomp.FIRST_CALL, site=self._prof_site):
            self._first_call_due = False
            return self._step_fn(*args)

    def step(self, *batch) -> jax.Array:
        from ..core import rng as rng_mod

        if self.abstract:
            raise RuntimeError(
                "This trainer was built from a LazyGuard (abstract) model "
                "— it can plan (memory_analysis / aot_lower) but not "
                "execute. Materialize the model (framework.lazy."
                "materialize) and rebuild the trainer to train.")
        if self._step_fn is None or self._n_batch_args != len(batch):
            self._build(len(batch))
        self._step += 1
        # zero-overhead-when-disabled guard: one bool read per step; the
        # instrumented branch additionally SYNCS on the loss (a host value
        # fetch), so the enabled step_ms histogram measures execution,
        # not dispatch.
        call = self._first_call if self._first_call_due else self._step_fn
        prof = _ptrace.is_enabled()
        sync = prof and getattr(self, "profiled_step_sync", True)
        t0 = time.perf_counter_ns() if prof else 0
        h2d = _ptrace.scope("hybrid/h2d") if prof else contextlib.nullcontext()
        with h2d:
            vs = self._stage_batch(batch)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        args = (*self._state_args(), vs, lr,
                jnp.asarray(self._step, jnp.int32), rng_mod.next_key())
        if self.guard_bad_steps:
            # fault defaults to the exact-noop 1.0; a pending injection
            # (inject_fault_scale) poisons exactly this one step
            args = args + (jnp.asarray(
                1.0 if self._fault_scale is None else self._fault_scale,
                jnp.float32),)
            self._fault_scale = None
        if prof:
            # profiled_step_sync (default True): sync on the loss so the
            # step_ms histogram measures execution, not dispatch. An
            # async-dispatch loop (elastic.py) sets it False — forcing a
            # per-step sync here would serialize the very overlap being
            # measured — and the deferred materialization records the
            # honest hybrid/sync_wait span instead; the histogram is
            # then named hybrid/dispatch_ms, because that is what it is.
            with _ptrace.scope("hybrid/step"):
                out = call(*args)
                if sync:
                    # truthful sync; the inner span isolates how much of
                    # the step was execution the host WAITED on vs
                    # dispatch (the gap the async pipeline hides)
                    with _ptrace.scope("sync_wait"):
                        float(np.asarray(out[0]))
            dt_ms = (time.perf_counter_ns() - t0) / 1e6
            reg = _preg()
            reg.counter("train/steps").add(1)
            reg.counter("train/tokens").add(_pinstr.tokens_in_batch(vs))
            reg.histogram("hybrid/step_ms" if sync
                          else "hybrid/dispatch_ms").observe(dt_ms)
            _pinstr.record_memory_high_water()
        else:
            out = call(*args)
        if self.guard_bad_steps:
            self._last_ok_dev = out[1]
            out = (out[0],) + out[2:]
        if self.stream_layers:
            (loss, self.block_vals, self.other_vals, self.block_comp,
             self.other_comp, self.block_opt, self.other_opt,
             self.aux_stats) = out
        else:
            (loss, self.block_vals, self.other_vals, self.block_opt,
             self.other_opt, self.aux_stats) = out
        if prof and sync and self.aux_stats:
            # outputs of the step whose loss was just waited for
            self.model.publish_aux_stats(jax.device_get(self.aux_stats))
        self.optimizer._global_step = self._step
        return loss

    __call__ = step

    # -- bad-step guard surface (paddle_tpu.resilience) --------------------
    @property
    def last_step_ok(self) -> bool:
        """Verdict of the most recent guarded step (True before any step
        or when the guard is off). Reading it syncs on the tiny verdict
        scalar — the resilient runner already syncs on the loss, so this
        costs nothing extra there."""
        if self._last_ok_dev is None:
            return True
        return bool(np.asarray(self._last_ok_dev))

    def last_step_ok_device(self):
        """The guarded verdict of the most recent step as the DEVICE
        scalar (None before any guarded step) — the async step
        pipeline's deferred-sync handle: the resilient runner captures
        it per dispatched step and materializes a whole window at its
        sync points instead of paying a host round-trip every step."""
        return self._last_ok_dev

    def inject_fault_scale(self, value: float) -> None:
        """Chaos hook: multiply the NEXT step's loss by ``value`` (NaN
        poisons loss and every gradient). One-shot; requires
        guard_bad_steps so the poison cannot reach the weights."""
        if not self.guard_bad_steps:
            raise RuntimeError(
                "inject_fault_scale requires guard_bad_steps=True — "
                "injecting a NaN without the guard would poison the "
                "weights permanently")
        self._fault_scale = float(value)

    def _stage_arg(self, b):
        v = b._value if isinstance(b, Tensor) else jnp.asarray(b)
        return jax.device_put(v, NamedSharding(
            self.mesh, self._batch_spec(v.ndim)))

    def _stage_batch(self, batch) -> tuple:
        """Device-put each batch element with the trainer's batch
        sharding — the ONE staging definition; step(),
        profile_step_phases() and aot_lower() must place batches
        identically or their programs would not cache-share."""
        return tuple(self._stage_arg(b) for b in batch)

    def profile_step_phases(self, *batch, iters: int = 2,
                            trace_window: int = 0):
        """Per-phase (fwd/bwd/optim/comm) decomposition of the train
        step, recorded as ``phase/*_ms`` gauges — what
        ``profiler.summary()["phases_ms"]`` reports.

        The step is ONE fused pjit program, so phases cannot be
        host-timed inside it; nested prefixes are compiled and timed
        instead — fwd (loss only), fwd+bwd (value_and_grad), full step —
        and bwd = fwdbwd − fwd, optim = step − fwdbwd. ``comm`` is a
        two-number split (profiler.instrument.record_phases): the
        nominal-bandwidth model (``phase/comm_ms`` — collective bytes
        over link rate) AND measured step wall time apportioned by
        XLA's cost-analysis byte accounting
        (``phase/comm_measured_ms`` — real clock, modeled
        attribution); both 0 on one chip. Also folds the step program's
        compile wall-time + cost-analysis FLOPs/bytes into the
        profiler's program inventory (xla_stats, keyed by the
        ``hybrid.step#N`` site). Costs three
        extra diagnostic compiles (fwd, fwd+bwd, and the timed
        inventory compile) and runs ``iters`` REAL optimizer steps
        (training state advances). Offload/stream configs skip the fwd/bwd split
        (their step streams host-resident state the sub-programs would
        misattribute) and report step + comm only.

        ``trace_window=k`` (ISSUE 11) additionally wraps ``k`` MORE
        real steps in a parsed device-trace capture
        (profiler.device_trace): measured per-op-category timings,
        per-collective durations by kind, the compute∩comm overlap
        fraction (``phase/comm_traced_ms`` / ``phase/comm_overlap_frac``
        — MEASURED, next to the apportioned ``phase/comm_measured_ms``)
        and the goodput/MFU ledger, returned under the ``"trace"`` key.
        On CPU the trace measures XLA:CPU thunks (host-scheduled —
        overlap ~0 by construction; stated in device_trace docs).
        """
        from ..core import rng as rng_mod

        if self._step_fn is None or self._n_batch_args != len(batch):
            self._build(len(batch))
        vs = self._stage_batch(batch)
        key = rng_mod.next_key()

        t_fwd = t_fb = None
        if not (self.stream_layers or self.offload_params):
            fwd = jax.jit(lambda bp, op: self._forward_loss(
                bp, op, vs, key)[0])
            t_fwd = _pinstr.time_compiled(
                lambda: fwd(self.block_vals, self.other_vals), iters)
            fb = jax.jit(lambda bp, op: jax.value_and_grad(
                lambda b_, o_: self._forward_loss(b_, o_, vs, key)[0],
                argnums=(0, 1))(bp, op))
            t_fb = _pinstr.time_compiled(
                lambda: fb(self.block_vals, self.other_vals), iters)
        t_step = _pinstr.time_compiled(lambda: self.step(*batch), iters)

        lowered = self.aot_lower(*batch)
        st = _pinstr.record_collectives_from(lowered, self.mesh)
        # compiled-program accounting: compile wall-time + XLA's own
        # cost analysis into the program inventory, keyed by the same
        # site name the retrace telemetry uses — and the cost-analysis
        # byte total turns the comm phase into a measured/estimated
        # split (phase/comm_measured_ms: measured step time apportioned
        # by collective-byte share) next to the nominal-bandwidth model
        from ..profiler import xla_stats as _xstats

        ps = _xstats.record_lowered(self._prof_site, lowered)
        out = _pinstr.record_phases(
            fwd_s=t_fwd, fwdbwd_s=t_fb, step_s=t_step,
            comm_bytes=st["total_bytes"], platform=_target_platform(),
            cost_bytes_accessed=ps.bytes_accessed)
        if trace_window:
            # record_lowered above registered the step program's HLO
            # module name, so the parsed slices attribute to
            # hybrid.step#N; each step syncs (time_compiled idiom) so
            # no device work is cut off when the trace stops
            from ..profiler import device_trace as _dtrace

            with _dtrace.capture(steps=int(trace_window),
                                 label=self._prof_site) as cap:
                for _ in range(int(trace_window)):
                    _pinstr._first_leaf(self.step(*batch))
            out["trace"] = cap.summary
        return out

    def memory_analysis(self, *batch):
        """Compiled-memory report of the train step (bytes), from XLA's
        buffer assignment: what the program needs, before it runs
        (``Device.memory_stats()["peak_bytes_in_use"]`` says what a run
        used). ``peak ≈ arguments − aliased + temps`` (donated state
        re-uses its argument buffers; offloaded state is host-resident
        and excluded from the HBM argument total by XLA's per-space
        accounting)."""
        ma = self.aot_compile(*batch).memory_analysis()
        if ma is None:
            return None
        out = {k: int(getattr(ma, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes") if hasattr(ma, k)}
        if {"argument_size_in_bytes", "temp_size_in_bytes",
                "alias_size_in_bytes"} <= out.keys():
            out["peak_bytes_est"] = (out["argument_size_in_bytes"]
                                     - out["alias_size_in_bytes"]
                                     + out["temp_size_in_bytes"])
        if self.offload_params or self.offload_optimizer:
            # split HBM vs host arguments (r3 "cannot split" note closed):
            # XLA's argument total folds pinned_host args in, but WE know
            # exactly which state the trainer placed host-side — subtract
            # its bytes to get the HBM-resident argument set.
            host = 0

            def nbytes(v):
                return int(np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize

            leaves = jax.tree_util.tree_leaves
            if self.offload_params:
                host += sum(nbytes(v) for v in leaves(self.block_vals))
                host += sum(nbytes(v) for v in leaves(self.other_vals))
            if self.offload_optimizer:
                host += sum(nbytes(v) for v in leaves(self.block_opt))
                host += sum(nbytes(v) for v in leaves(self.other_opt))
            out["host_resident_argument_bytes"] = host
            args = out.get("argument_size_in_bytes", 0)
            if args >= host:
                out["hbm_argument_bytes"] = args - host
                if "peak_bytes_est" in out:
                    out["hbm_peak_bytes_est"] = max(
                        out["peak_bytes_est"] - host, 0)
            else:
                # this toolchain build already excluded host-space args
                # from its per-space totals — subtracting again would
                # double-count (seen at 1.9B: args < host bytes)
                out["hbm_argument_bytes"] = args
                if "peak_bytes_est" in out:
                    out["hbm_peak_bytes_est"] = out["peak_bytes_est"]
        return out

    def aot_lower(self, *batch):
        """AOT-lower the train step without executing anything. ``batch``
        entries may be Tensors, arrays, or ``jax.ShapeDtypeStruct``s
        (required in abstract/LazyGuard mode — nothing is materialized
        anywhere in that path)."""
        if self._step_fn is None or self._n_batch_args != len(batch):
            self._build(len(batch))
        vs = []
        for b in batch:
            if isinstance(b, jax.ShapeDtypeStruct):
                vs.append(jax.ShapeDtypeStruct(
                    tuple(b.shape), b.dtype, sharding=NamedSharding(
                        self.mesh, self._batch_spec(len(b.shape)))))
            else:
                vs.append(self._stage_arg(b))
        # constant key: only avals matter for lowering, and a diagnostic
        # must not advance the training RNG stream. suppressed(): this
        # re-trace is by design, not a silent recompile — keep it out of
        # the profiler's retrace counter/log.
        tail = ((jax.ShapeDtypeStruct((), jnp.float32),)
                if self.guard_bad_steps else ())
        with _precomp.suppressed():
            return self._step_fn.lower(
                *self._state_args(), tuple(vs),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((2,), jnp.uint32), *tail)

    def aot_compile(self, *batch):
        return self.aot_lower(*batch).compile()

    # -- sharded checkpoint integration (distributed/checkpoint.py) -------
    def device_state(self):
        """The trainer's on-device state as one pytree of sharded arrays
        (params + optimizer state), for distributed.checkpoint.save."""
        return {"block": dict(self.block_vals),
                "other": list(self.other_vals),
                "block_opt": {k: list(v) if isinstance(v, list)
                              else dict(v)
                              for k, v in self.block_opt.items()},
                "other_opt": [dict(d) for d in self.other_opt]}

    def load_device_state(self, st, step: Optional[int] = None):
        """Inverse of device_state (resume-exact: same values, shardings)."""
        self.block_vals = dict(st["block"])
        self.other_vals = list(st["other"])
        self.block_opt = {k: list(v) if isinstance(v, list) else dict(v)
                          for k, v in st["block_opt"].items()}
        self.other_opt = [dict(d) for d in st["other_opt"]]
        if self.stream_layers and self.offload_params \
                and self.comp_resident:
            # the bf16 compute copies are derived state (comp ≡
            # bf16(master) after every update) — rebuild, don't persist
            def dev_bf16(p, spec):
                d = jax.device_put(p, NamedSharding(self.mesh, spec))
                return d.astype(jnp.bfloat16) \
                    if jnp.issubdtype(d.dtype, jnp.floating) else d

            self.block_comp = {
                sfx: jax.device_put(
                    jnp.stack([dev_bf16(p, self.block_layer_specs[sfx])
                               for p in pieces], 1),
                    NamedSharding(self.mesh, self.block_specs[sfx]))
                for sfx, pieces in self.block_vals.items()}
            self.other_comp = [
                jax.device_put(dev_bf16(v, spec),
                               NamedSharding(self.mesh, spec))
                for v, spec in zip(self.other_vals, self.other_specs)]
        if step is not None:
            self._step = int(step)
            self.optimizer._global_step = int(step)

    def memory_ledger(self) -> dict:
        """Per-rank resident bytes by state category, from ACTUAL array
        shardings (profiler.record_memory_ledger — gauges
        ``mem/{param,grad,opt_state,master}_bytes``). On the manual
        ZeRO path opt state (and master) are [dp*chunk] slabs sharded
        P('dp'), so their per-rank count is 1/dp of the replicated
        baseline; ``grad`` is the transient fused gradient buffer,
        counted at its full-size per-rank peak (pre-reduce-scatter)."""
        params = (self.block_vals, self.other_vals)
        cats = {"param": params,
                "grad": 4 * sum(int(np.prod(v.shape))
                                for v in jax.tree_util.tree_leaves(
                                    params))}
        if self.zero_manual:
            slab = self.block_opt[_ZERO_SLAB]
            cats["opt_state"] = {k: v for k, v in slab.items()
                                 if k != "master"}
            if "master" in slab:
                cats["master"] = slab["master"]
        else:
            cats["opt_state"] = (self.block_opt, self.other_opt)
        return _pinstr.record_memory_ledger(cats)

    def _unflatten_zero_opt(self):
        """Regather the fused dp-sharded ZeRO slabs and slice them back
        into the per-suffix / per-other optimizer-state layout
        (host-side; sync_to_layer path only). Slice order is the
        tree_flatten order the slabs were built in: sorted block
        suffixes, then the other-param list."""
        flat = {k: np.asarray(v)
                for k, v in self.block_opt[_ZERO_SLAB].items()
                if k != "master"}
        blk, oth, off = {}, [], 0
        for sfx in sorted(self.block_vals.keys()):
            shape = tuple(self.block_vals[sfx].shape)
            sz = int(np.prod(shape))
            blk[sfx] = {k: jnp.asarray(v[off:off + sz].reshape(shape))
                        for k, v in flat.items()}
            off += sz
        for v in self.other_vals:
            shape = tuple(v.shape)
            sz = int(np.prod(shape))
            oth.append({k: jnp.asarray(v2[off:off + sz].reshape(shape))
                        for k, v2 in flat.items()})
            off += sz
        return blk, oth

    def sync_to_layer(self):
        """Unstack device state (params AND optimizer accumulators) back
        into the eager model/optimizer, so state_dict/checkpoints see the
        trained values."""
        L = self.n_layers
        blk_opt_src, oth_opt_src = (self._unflatten_zero_opt()
                                    if self.zero_manual
                                    else (self.block_opt,
                                          self.other_opt))

        def unstack(a):
            if isinstance(a, list):
                # stream_layers per-layer pieces [pp, ...] → [pp, lps, ..]
                a = jnp.stack(
                    [jax.device_put(
                        p, NamedSharding(self.mesh, p.sharding.spec))
                     if getattr(p.sharding, "memory_kind", None)
                     == "pinned_host" else p for p in a], 1)
            if getattr(a.sharding, "memory_kind", None) == "pinned_host":
                a = jax.device_put(
                    a, NamedSharding(self.mesh, a.sharding.spec))
            if self.v == 1:
                return a.reshape((L,) + tuple(a.shape[2:]))
            # invert the circular assignment: [pp, v, lps_v, ...] -> [L,...]
            return jnp.swapaxes(a, 0, 1).reshape((L,) + tuple(a.shape[3:]))

        for sfx_i, sfx in enumerate(self.block_suffixes):
            stacked = self.block_vals[sfx]
            flat = unstack(stacked)
            opt_src = blk_opt_src[sfx]
            if isinstance(opt_src, list):   # stream per-layer dicts
                opt_src = {k: [d[k] for d in opt_src]
                           for k in opt_src[0]}
            opt_flat = {k: unstack(v) for k, v in opt_src.items()}
            for i in range(L):
                t = self._per_block_tensors[i][sfx_i]
                t._value = flat[i]
                self.optimizer._accumulators[id(t)] = {
                    k: v[i] for k, v in opt_flat.items()}
        for n, v, s in zip(self.other_names, self.other_vals,
                           oth_opt_src):
            t = self._name2tensor[n]
            if getattr(v.sharding, "memory_kind", None) == "pinned_host":
                v = jax.device_put(
                    v, NamedSharding(self.mesh, v.sharding.spec))
            t._value = v
            self.optimizer._accumulators[id(t)] = s
        return self.model
