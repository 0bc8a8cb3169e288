"""Continuous-batching serving engine over the paged KV cache.

The dense ``GPT.generate`` path is one jitted prefill+scan program per
request batch: every admitted prompt pays ``S_max`` of cache HBM,
nobody can join or leave mid-decode, and mixed prompt lengths force
padding waste or a retrace. This engine restructures serving the way
the roadmap's cross-replica-sharding paper restructures the weight
update — so the hardware never idles on work another request could
fill:

- **ONE unified mixed-row tick.** A single jitted program per
  scheduler step carries EVERY token in flight as a ragged row —
  resident decodes (one-token rows) and up to
  ``prefill_chunks_per_tick`` prompt chunks (``prefill_chunk``-token
  rows) execute in the same program, through one
  ``ops/paged_attention.ragged_paged_attention`` call per layer over
  per-row ``(pos0, true_len)`` metadata ("Ragged Paged Attention",
  PAPERS.md). The program shape never depends on
  the prefill/decode mix, so it traces exactly once (asserted via
  ``profiler.recompile`` telemetry). Per-request sampling params ride
  as ``[num_slots]`` arrays — no retrace per parameter combination.
  The page pools travel through it as ONE argument
  (``paged_cache.Pools``, donated and stored back); what a pool is —
  two arrays, or four with int8 scales — is that module's business.
  Inside, the tick is ONE body for every mix (no decode-only branch):
  the pools are the carry of the forward's scan over layers, written
  and read by ``(layer, page[, offset])`` and updated in place, so the
  compiled tick holds no pool-sized temporary (the gauges
  ``serving/tick_temp_bytes`` / ``serving/tick_alias_bytes`` say so at
  the first dispatch); on a tick without a chunk the chunk rows ride as
  pad rows and only their attention is skipped.
- **Chunked prefill** (Sarathi-style piggybacking). A prompt is
  prefilled in fixed-size chunks riding the unified tick, at most
  ``prefill_chunks_per_tick`` per scheduler step, each attending over
  (aliased prefix pages + earlier chunks + itself). A long prompt
  therefore never blocks resident decode slots for more than one
  chunk's compute, and chunks add ZERO extra dispatches or compiled
  programs.
- **Prefix caching.** Fully-written prompt pages are registered in a
  hash-trie index (``paged_cache.PrefixCache``) keyed on page-aligned
  token chunks. Admission looks up the longest cached prefix, aliases
  those pages into the slot's table (refcounted — a page frees only
  when its last holder lets go), and prefills only the suffix; a
  prompt diverging from a cached chunk mid-page copy-on-writes that
  one tail page. Unreferenced cached pages are evicted LRU under pool
  pressure. Preemption inserts the victim's own fully-written pages
  before releasing the slot, so the requeued request re-aliases its
  own work instead of re-prefilling it.
- **Deferred host sync** (the PR-3 async-pipeline idiom): each tick's
  token vector stays an unmaterialized device array; the host
  dispatches tick N+1 before materializing tick N, keeping up to
  ``max_inflight`` ticks in flight. Scheduling that must be
  host-deterministic (positions, page growth, max-token stops) never
  reads device data; only EOS discovery rides the lagged window.
- **Exhaustion → eviction → preemption.** If the pool cannot grow a
  slot, the engine evicts unreferenced cached pages, drains, retries,
  then preempts the youngest request: its generated prefix is requeued
  as a longer prompt (and its pages stay cached, so re-prefill is a
  prefix hit). Sampling keys are folded per absolute position, so a
  preempted request's tokens do not depend on scheduling.
- **Quantized KV pages** (``ServingConfig.kv_dtype``; ISSUE 12):
  ``"int8"`` stores the pools as int8 with per-page per-head f32
  scales — 4x tokens per pool byte (2x resident slots at matched
  bytes with headroom to spare). Quantize-on-write rides INSIDE the
  one tick (``ops/paged_attention.paged_kv_scatter``: running
  scatter-max scales, rescale-on-growth, recycled pages reset via the
  fresh-page vector folded into the tick args), dequantization rides
  inside the one shared attention gather, and scales travel every
  refcount edge (COW copies the donor's scales; the null page keeps
  scale 0). ``compiled_sites`` is unchanged — int8 is a dtype of the
  one mixed-row tick, not a new dispatch site. Greedy parity vs the
  f32 engine becomes a measured token-match rate (``serve_bench
  --kv-dtype``); two int8 engines still agree bitwise. ``"bf16"``
  halves the pool with a plain cast.
- **Speculative decoding** (``ServingConfig.spec``; serving/spec.py):
  a draft model runs ``k`` tokens ahead per slot, ONE verify/mixed
  tick scores every slot's ``(1+k)``-token row (a verify row is a
  chunk row whose logits are kept at every position), greedy
  acceptance emits the target's own argmax stream — so spec greedy is
  BITWISE plain greedy — and rejected tails rewind through the
  refcounted ``shrink_slot`` path. Two compiled sites (draft tick +
  verify tick), per-tick host sync instead of the deferred window.

Greedy paged decode is **bitwise identical** to the dense
``generate()`` on the same weights whenever the slot capacity
``pages_per_slot * page_size`` equals the dense path's
``prompt + max_new_tokens`` (the attention reduction length must match
exactly — zero-tail padding is not bitwise-neutral). The unified tick
preserves this: per-token results are independent of which other rows
share the program (see ``gpt_ragged_apply``'s contract), and prefix
caching preserves it too (aliased pages hold KV that is identical by
construction), so the cached engine, the uncached engine and the
dense path all agree — tests/test_serving.py pins cached-vs-uncached
across admission orders.

Profiler signals: ``serving/queue_depth``, ``serving/active_slots``,
``serving/page_util``, ``serving/ttft_ms`` (histogram),
``serving/prefill_queue_wait_ms`` (histogram: submit → first prefill
chunk, FRESH admissions only), ``serving/requeue_wait_ms`` (histogram:
preempt → re-prefill start — requeue cycles used to fold back into the
submit-anchored wait, conflating scheduler delay with preemption
cost), ``serving/tokens_generated``,
``serving/prefills``, ``serving/prefill_chunks``, ``serving/ticks``,
``serving/ticks_without_chunk`` (the unified ticks told ``has_chunks``
false),
``serving/tick_temp_bytes`` / ``serving/tick_alias_bytes`` (gauges, set
once when the tick is first compiled: its ``memory_analysis()``; in
place means temporaries far under one pool and every pool aliased),
``serving/cache_layers`` / ``serving/weights_bytes`` (gauges, set once:
the pools' depth, ``num_layers`` x the model's ``loop_steps``, and the
bytes of weights the engine holds on the device), of a looped model
``loop/steps_run`` (counter: loop steps x ticks) and
``loop/expected_exit_step`` / ``loop/chosen_exit_step`` (gauges: mean over
the rows sampled since ``exit_steps()`` last read them, from the tick's
own outputs, set when a tick is drained),
``serving/preemptions``, ``serving/requests_finished``,
``serving/drain_waited`` / ``serving/drain_ready`` (drained ticks whose
tokens the host had to wait for / found ready),
``serving/tick_turnaround_ms`` (histogram, one observation a drained
tick: dispatch to tokens on the host; both fed from the tick log's clock
reads), ``serving/holds{kind=}`` / ``serving/hold_ms{kind=}`` /
``serving/hold_lost_ms`` (the stalls of the serving loop the tick log
named: ``tick_log()``, profiler/ticklog.py, one ``hold`` event each),
``serving/submit_ms``
(histogram: host time of each ``submit()``), ``serving/prefix_lookups``,
``serving/prefix_hit_tokens``, ``serving/mixed_rows`` (+ the
``_decode``/``_prefill`` split: rows of each kind in the last unified
tick — a dispatch-site regression shows up here and in the
``serving.tick`` single-trace assertion); refcount traffic under
``cache_share/*`` (shares, releases, cow_copies, prefix_evictions).
Scheduler-policy signals (ISSUE 15): ``serving/chunk_wait_ms``
(histogram: admission -> first chunk open per admission cycle),
``serving/aged_promotions`` (aged-sjf picks pure SJF would have
ordered differently), ``serving/budget_cuts`` (ticks whose shaped
prefill budget came in under the compiled worst case),
``serving/spec_k_effective`` (mean offered draft depth per spec
tick under adaptive k).

Event timeline (ISSUE 8; profiler/events.py): every request lifecycle
edge emits a typed event into the profiler's bounded event log —
``submit``, ``admit``, ``prefix_hit``, ``cow_copy``, ``chunk`` (one
per dispatched prefill chunk), ``first_token``, ``preempt``,
``requeue``, ``finish`` (stamped with ``ttft_ms``/``tpot_ms``/
``tokens``/``reason``) — each tagged with the engine id (``eng``) and
request id, so ``profiler.latency_breakdown(rid)`` reconstructs queue
wait / prefill / decode / preempted time per request and
``ServingEngine.latency_stats(window_s=...)`` reports rolling-window
TTFT/TPOT p50/p90/p95/p99. Emission is lifecycle-edge-rate (O(1) per
residency period, never per token or per tick), so the decode hot
loop pays one bool read; serve_bench measures the residual honestly.
``record_program_stats()`` folds each compiled hot-path program's
compile wall-time + ``cost_analysis()`` FLOPs/bytes into the
profiler's program inventory, keyed by ``compiled_sites``.
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.paged_attention import live_block_share
from ..profiler import events as _events
from ..profiler import recompile as _recompile
from ..profiler import registry as _registry
from ..profiler import ticklog as _ticklog
from ..profiler import trace as _ptrace
from .paged_cache import Pools, page_pool
from .sched import SCHED_POLICIES, ChunkScheduler, SpecKController
from .spec import SpecConfig

__all__ = ["ServingConfig", "ServingEngine", "Request", "SpecConfig"]

#: engine ids stamped on every event (``eng`` attr) so co-resident
#: engines' timelines don't alias in the process-global log
_ENGINE_SEQ = iter(range(1 << 20))
_NOTHING = nullcontext()


#: a request's default sampling key: the request id folded into the
#: engine's base key on the host's CPU backend, where ``__init__`` commits
#: the base key. ``submit()`` calls it, so a request puts nothing on the
#: serving device's queue and waits for nothing behind the ticks in flight
_fold_key = jax.jit(jax.random.fold_in)


def _host_device():
    """This process's own CPU device (under ``jax.distributed`` the global
    list starts with process 0's, which no other rank can address)."""
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "ServingEngine makes each request's sampling key on the host's "
            "CPU backend, so that submit() never queues behind the ticks in "
            "flight on the accelerator, and this process has no CPU backend: "
            "leave JAX_PLATFORMS unset, or name cpu after the accelerator "
            f"(JAX_PLATFORMS=tpu,cpu); jax said: {e}") from e


def _proc_index() -> int:
    """The jax process index (0 when jax.distributed never came up) —
    folded into engine ids so co-resident engines ACROSS processes of a
    multi-host mesh stop colliding in merged ``latency_table()`` views
    (PR 8 noted the per-process sequence already reuses ids across
    processes; rank-merged sinks made that visible). ONE detection
    helper: the sink's, which guards against forcing backend bring-up
    when jax.distributed was never initialized."""
    from ..profiler.sink import _detect_rank

    return _detect_rank()


@contextmanager
def _quiet_donation():
    """XLA:CPU declines buffer donation for the page pools; the
    fallback copy is correct there — don't spam the log for it. On any
    other backend a declined pool donation is a full pool copy per
    tick, so the warning is left to be seen (chip_smoke.py fails on
    it). Scoped to the engine's own dispatches: a global filter would
    also swallow the training stack's donation-failure warnings (a
    real perf signal in hybrid.py's jitted step)."""
    if jax.default_backend() != "cpu":
        yield
        return
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


@dataclass
class ServingConfig:
    """Engine knobs. Pool sizing math: the pool holds
    ``num_pages - 1`` allocatable pages (page 0 is the null page) of
    ``page_size`` tokens each, shared by ``num_slots`` resident
    requests of at most ``pages_per_slot`` pages
    (``slot_capacity = pages_per_slot * page_size`` tokens). Sizing
    ``num_pages - 1 < num_slots * pages_per_slot`` oversubscribes the
    pool — legal, served by prefix-cache eviction then preemption when
    it binds. With ``prefix_cache`` on, shared prompt pages are charged
    ONCE regardless of how many slots alias them, so effective
    capacity grows with prompt overlap."""

    num_slots: int = 8
    page_size: int = 16
    pages_per_slot: int = 0          # default: ceil(max_seq_len / page_size)
    num_pages: int = 0               # default: full residency + null page
    prefill_chunk: int = 0           # tokens per prefill chunk (0: 2 pages)
    prefill_chunks_per_tick: int = 1  # prefill rows per unified tick
    #: chunk-selection policy (ISSUE 15; serving/sched.py): 'fifo'
    #: (oldest admission first — the default, scheduling bit-for-bit
    #: the pre-policy engine's so every bitwise parity pin is
    #: undisturbed), 'sjf' (shortest-remaining-prefill first) or
    #: 'aged-sjf' (SJF + deadline aging with a provable starvation
    #: bound). Non-fifo policies also shape the per-tick prefill
    #: budget from decode-stall telemetry, capped at the compiled
    #: ``prefill_chunks_per_tick`` worst case — the tick shape never
    #: retraces. Host-side only: per-request outputs stay bitwise
    #: identical under every policy; only the interleaving moves.
    scheduler: str = "fifo"
    prefix_cache: bool = True        # share prompt-prefix pages
    max_inflight: int = 2            # unmaterialized decode ticks in flight
    decode: str = "greedy"           # 'greedy' | 'sampling'
    #: page-pool storage dtype (ISSUE 12): None keeps the model dtype
    #: (the bitwise-parity default), 'f32'/'bf16' store at that dtype,
    #: 'int8' quantizes pages on write with per-page per-head scales —
    #: 4x tokens per pool byte vs f32, greedy parity becomes a measured
    #: token-match rate (serve_bench --kv-dtype) instead of bitwise.
    kv_dtype: Optional[str] = None   # None | 'f32' | 'bf16' | 'int8'
    temperature: float = 1.0         # sampling defaults; per-request
    top_k: int = 0                   #   overrides ride submit()
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    seed: int = 0
    #: speculative decoding (serving/spec.py SpecConfig: draft model +
    #: k). The engine gains a second
    #: compiled site (the draft tick) and syncs each verify tick —
    #: acceptance decides the next tick's positions, so the deferred
    #: window cannot stay open across it (max_inflight is ignored).
    spec: Optional[SpecConfig] = None


@dataclass
class Request:
    rid: int
    prompt: np.ndarray               # current prompt (grows on preemption)
    max_new: int                     # tokens still wanted (shrinks on preempt)
    key: np.ndarray                  # uint32[2] sampling key (absolute-pos folds)
    out: List[int] = field(default_factory=list)
    done: bool = False
    #: prefill-group mode (ISSUE 13): stop after the prompt is fully
    #: prefilled and the FIRST token sampled — the request's KV pages
    #: are then exported to a decode-group engine instead of decoding
    #: here. Survives preemption (the requeued victim re-prefills and
    #: holds again).
    hold: bool = False
    submit_t: float = 0.0
    due_t: Optional[float] = None    # when it was due, if the driver said
    queue_t: float = 0.0             # (re)queue anchor: submit, or requeue
    preempts: int = 0                # times this request was preempted
    first_token_t: Optional[float] = None
    orig_prompt_len: int = 0         # for result accounting across preemption
    temperature: Optional[float] = None   # per-request sampling overrides
    top_k: Optional[int] = None           #   (None -> engine config default)
    top_p: Optional[float] = None
    #: cross-host trace id (ISSUE 14): stamped as a ``trace`` attr on
    #: every lifecycle event this engine emits for the request, and
    #: carried across the KV handoff so the decode rank's events join
    #: the same trace. None (local-only request) emits no attr.
    trace_id: Optional[str] = None
    #: abandoned without a result (ISSUE 17 orphan bookkeeping): set
    #: by ``cancel()`` when the mesh re-dispatched this gid elsewhere —
    #: ``done`` is True so the scheduler forgets it, but it must never
    #: surface as a served output (``run()``/coordinators skip it)
    canceled: bool = False

    @property
    def ttft_from(self) -> float:
        """Where ``serving/ttft_ms`` runs from: the due time if the
        driver gave one, else the submit."""
        return self.submit_t if self.due_t is None else self.due_t


class _Inflight(NamedTuple):
    tok: jax.Array                   # device int32 array
    meta: list                       # [(index_into_tok, slot, rid)]
    tick: int                        # the engine's tick that computes it
    #: what the tick reports of itself beside its tokens (device arrays by
    #: name; the model's record reads them) and the cache position each
    #: sampled row's query stood at
    aux: dict
    positions: np.ndarray
    #: the tick log's row of this tick, and its ``t_dispatch``
    row: int
    dispatch_ns: int


#: one selected-but-not-yet-dispatched prompt chunk of the unified tick
_Chunk = Tuple[int, int, int, int, int]   # (slot, rid, start, end, t0)


class ServingEngine:
    """Continuous-batching serving runtime for a model that gives
    ``cache_spec()``, ``_decode_state()`` and ``ragged_apply(...)``
    (``models/tick.py`` states the protocol).

    ::

        eng = ServingEngine(model, ServingConfig(num_slots=8))
        rid = eng.submit(prompt_ids, max_new_tokens=32)
        out = eng.run()[rid]          # np.int32 generated ids
    """

    def __init__(self, model, config: Optional[ServingConfig] = None):
        # compiled programs: ONE mixed-row tick site serving decodes
        # AND prefill chunks, asserted single-trace (spec decoding: the
        # verify tick, plus the draft tick's site)
        self._tick_site = _recompile.unique_site("serving.tick")
        # set-up on the always-on record (profiler/trace.py ``phase``)
        with _ptrace.phase("setup/engine", site=self._tick_site):
            self._construct(model, config or ServingConfig())

    def _construct(self, model, cfg: ServingConfig):
        mcfg = model.config
        if cfg.decode not in ("greedy", "sampling"):
            raise ValueError(f"unknown decode mode {cfg.decode!r}")
        if cfg.prefill_chunks_per_tick < 1:
            raise ValueError("prefill_chunks_per_tick must be >= 1")
        if cfg.scheduler not in SCHED_POLICIES:
            raise ValueError(
                f"unknown scheduler {cfg.scheduler!r}; expected one of "
                f"{SCHED_POLICIES}")
        self._spec = cfg.spec
        if self._spec is not None:
            if self._spec.overlap and cfg.decode != "sampling":
                raise ValueError(
                    "spec.overlap chains the next draft tick on the "
                    "sampled verify tick's device outputs; greedy spec "
                    "has no chained draft build — use decode='sampling'")
            if self._spec.k < 1:
                raise ValueError("spec.k must be >= 1")
        # process index folded in: ids stay unique when rank-tagged
        # event streams from N processes are merged (ISSUE 13)
        self._eng_id = (_proc_index() << 20) | next(_ENGINE_SEQ)
        #: the always-on record of every tick and the holds it names
        #: (profiler/ticklog.py); ``step``, ``_dispatch_unified`` and
        #: ``_drain`` write it, and read no clock of their own
        self._ticks = _ticklog.TickLog(self._eng_id)
        # {site: (jitted fn, arg avals)} captured at first dispatch —
        # record_program_stats() re-lowers from these for cost analysis
        self._program_args: Dict[str, tuple] = {}
        self.config = cfg
        self.model_config = mcfg
        # the second, stacked copy of the weights (under ``LazyGuard``
        # the only one: drawn here)
        with _ptrace.phase("setup/engine/decode_state"):
            self._stacked, self._other = model._decode_state()
        self._dtype = self._other["embeddings.wte.weight"].dtype
        #: what caches the model keeps and what its ticks report, from the
        #: model (``models/tick.py``)
        caches = model.cache_spec()
        self._apply = model.ragged_apply
        #: a looped model runs its layers ``loop_steps`` times a tick and
        #: keeps a cache for every (step, layer)
        self._loop_steps = caches.get("loop_steps", 1)
        if self._loop_steps > 1 and self._spec is not None:
            raise NotImplementedError(
                "speculative decoding of a looped model: the verify tick "
                "(serving/spec.py make_spec_tick) reads no exit step and "
                "the draft runner's pools are the draft's num_layers deep")
        # page-pool storage dtype (ISSUE 12): None follows the model
        kv_map = {None: self._dtype, "f32": jnp.float32,
                  "bf16": jnp.bfloat16, "int8": jnp.int8}
        if cfg.kv_dtype not in kv_map:
            raise ValueError(
                f"unknown kv_dtype {cfg.kv_dtype!r}; expected one of "
                "None (model dtype), 'f32', 'bf16', 'int8'")
        ps = cfg.page_size
        pages_per_slot = cfg.pages_per_slot or -(-mcfg.max_seq_len // ps)
        num_pages = cfg.num_pages or cfg.num_slots * pages_per_slot + 1
        self.prefill_chunk = int(cfg.prefill_chunk) or 2 * ps
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        #: the model's record of what its ticks report of themselves
        #: (``models/tick.py``); None: its ticks report nothing
        record = caches.get("tick_record")
        self.tick_record = record() if record is not None else None
        with _ptrace.phase("setup/engine/pools"):
            self.pool = page_pool(
                caches, num_pages, ps, cfg.num_slots, pages_per_slot,
                self.prefill_chunk, kv_map[cfg.kv_dtype], cfg.prefix_cache,
                rewinds=cfg.spec is not None,
                chunk_rows=cfg.prefill_chunks_per_tick)
        # set once: how deep the pools are, and what the engine holds on
        # the device for the model (one copy of the weights, as served)
        _registry().gauge("serving/cache_layers").set(
            float(self.pool.num_layers))
        _registry().gauge("serving/weights_bytes").set(float(sum(
            a.nbytes for a in jax.tree_util.tree_leaves(
                (self._stacked, self._other)))))
        b_slots = cfg.num_slots
        # chunk-selection + budget policy (ISSUE 15; serving/sched.py)
        # — host-side only: picks which slot opens the next prefill
        # chunk and how many chunks this tick selects, never what any
        # compiled program looks like
        self._sched = ChunkScheduler(
            cfg.scheduler, b_slots, self.pool.slot_capacity,
            self.prefill_chunk, cfg.prefill_chunks_per_tick)
        # host scheduling state (never reads device data)
        self._slot_rid: List[Optional[int]] = [None] * b_slots
        self._slot_len = np.zeros(b_slots, np.int32)      # tokens in cache
        self._slot_prompt = np.zeros(b_slots, np.int32)   # current prompt len
        self._slot_dispatched = np.zeros(b_slots, np.int64)  # tokens emitted
        self._slot_admit_seq = np.zeros(b_slots, np.int64)
        self._slot_admit_t = np.zeros(b_slots, np.float64)
        #: latch: this admission cycle still owes its chunk-wait
        #: sample (recorded at the first chunk that actually OPENS —
        #: a selection whose page acquisition freed the slot opened
        #: nothing and must not count as service)
        self._slot_wait_due = [False] * b_slots
        #: per-ENGINE admission->first-chunk waits (bounded recent
        #: window) next to the registry-global serving/chunk_wait_ms
        #: histogram — co-resident engines (e.g. a policy matrix)
        #: share the registry, so per-engine evidence needs its own
        #: samples
        self.chunk_waits_ms: deque = deque(maxlen=1024)
        self._slot_looked_up = [False] * b_slots
        self._admit_seq = 0
        self._queue: deque[Request] = deque()
        self._requests: Dict[int, Request] = {}
        self._next_rid = 0
        self._inflight: deque[_Inflight] = deque()
        #: ticks dispatched so far: the ``tick`` id of the ``pt:step/*``
        #: spans, laid against the device's runs of the tick program
        self._tick_no = 0
        #: held requests whose first token has materialized — ready for
        #: export_held() (disaggregated prefill group, ISSUE 13)
        self._held_ready: set = set()
        self.max_inflight_seen = 0
        # device state
        self._last_tok = jnp.zeros((b_slots,), jnp.int32)
        self._keys = np.zeros((b_slots, 2), np.uint32)
        # per-slot sampling params (fixed-shape tick arguments)
        self._temps = np.full(b_slots, cfg.temperature, np.float32)
        self._topks = np.full(b_slots, cfg.top_k, np.int32)
        self._topps = np.full(b_slots, cfg.top_p, np.float32)
        # on this process's CPU device, with ``_fold_key`` compiled for it
        # now: ``submit()`` folds there, and compiles nothing under traffic
        self._base_key = jax.device_put(jax.random.PRNGKey(cfg.seed),
                                        _host_device())
        _fold_key(self._base_key, np.uint32(0))
        if self._spec is not None:
            from .spec import DraftRunner, make_spec_tick

            dcfg = self._spec.draft_model.config
            if dcfg.vocab_size != mcfg.vocab_size:
                raise ValueError(
                    f"draft vocab_size {dcfg.vocab_size} != target "
                    f"{mcfg.vocab_size}: acceptance compares token ids")
            if dcfg.max_seq_len < mcfg.max_seq_len:
                raise ValueError(
                    f"draft max_seq_len {dcfg.max_seq_len} must cover "
                    f"the target's {mcfg.max_seq_len}")
            self._spec_k = int(self._spec.k)
            #: adaptive per-slot draft depth (ISSUE 15; sched.py):
            #: accept-rate EWMA -> depth in the compiled [0, k] range
            #: the verify tick already supports via row_len. None =
            #: static k (the PR 9 behavior).
            self._spec_ctl = (
                SpecKController(b_slots, self._spec_k,
                                self._spec.ewma_alpha,
                                self._spec.reprobe_every)
                if self._spec.adaptive else None)
            #: per-tick cache of tick_depth() results — the probe
            #: state machine advances once per slot per tick even
            #: though depth is consulted at both the draft-feed and
            #: the ks-clamp points
            self._spec_tick_depth: Dict[int, int] = {}
            #: sampled spec decoding (ISSUE 20): the verify tick runs
            #: the rejection-sampling acceptance kernel instead of the
            #: greedy longest-argmax-prefix rule
            self._spec_sampling = cfg.decode == "sampling"
            #: hide the host-side accept/absorb sync under the NEXT
            #: draft tick: dispatch the chained draft build against the
            #: verify tick's still-on-device outputs before
            #: materializing them (sampling only)
            self._spec_overlap = bool(self._spec.overlap)
            #: pending chained draft state: dict with device drafts /
            #: probs plus host validity mask, or None when no chained
            #: tick is in flight
            self._spec_pend: Optional[dict] = None
            self._draft = DraftRunner(
                self._spec.draft_model, b_slots,
                self.pool.slot_capacity, self._spec_k,
                self.prefill_chunk, self.pool)
            #: per-admission-cycle lifecycle-event latches
            self._spec_started = [False] * b_slots
            self._spec_verifying = [False] * b_slots
            self._zero_drafts = np.zeros(b_slots * self._spec_k,
                                         np.int32)
            if self._spec_sampling:
                # draft-probs placeholder for ticks where no slot was
                # offered drafts (n_draft == 0 everywhere => unread)
                self._zero_probs = np.zeros(
                    (b_slots, self._spec_k, mcfg.vocab_size),
                    np.float32)
            tick = make_spec_tick(mcfg, b_slots, self._spec_k,
                                  self.prefill_chunk, self._tick_site)
        else:
            tick = self._make_unified_tick()
        # the pools (argument 2 of either tick) are donated and the
        # tick's first output is stored back; so with the page-granular
        # maintenance programs: COW copy, handoff import (export only
        # reads). None is a hot-path dispatch site
        self._tick = jax.jit(tick, donate_argnums=2)
        self._copy = jax.jit(Pools.copy_page, donate_argnums=0)
        #: the page copy's first call is a phase of its own site
        self._copy_site = self._tick_site.replace("tick", "copy_page")
        self._copy_called = False
        self._import_fn = jax.jit(Pools.write_pages, donate_argnums=0)
        self._export_fn = jax.jit(Pools.gather_pages)
        # size of the fresh-page reset vector folded into every tick of
        # pools with scales (paged_cache.take_fresh): past the worst
        # case one scheduler step can allocate — decode growth (<= 1
        # page per slot), speculation growth, and the selected chunks'
        # pages — so the eager-reset overflow path never triggers in
        # normal operation (it stays correct if it does). +1 covers
        # draft-page rewind churn: freed draft pages re-enter the fresh
        # list via the allocator's on_zero hook
        spec_extra = (self._spec_k // ps + 3) \
            if self._spec is not None else 0
        self._fresh_cap = (
            b_slots * (1 + spec_extra)
            + cfg.prefill_chunks_per_tick
            * (self.prefill_chunk // ps + 2) + 8)

    @property
    def compiled_sites(self) -> Tuple[str, ...]:
        """Recompile-telemetry site names of this engine's hot-path
        dispatch programs — the engine has exactly ONE (the
        mixed-row tick); a spec-decoding engine has exactly TWO (the
        draft tick + the verify/mixed tick). Tests assert this, so
        silently re-growing a dispatch site is a visible regression."""
        if self._spec is not None:
            return (self._tick_site, self._draft.site)
        return (self._tick_site,)

    def _emit(self, kind: str, rid: int, **attrs) -> None:
        req = self._requests.get(rid)
        if req is not None and req.trace_id is not None:
            attrs.setdefault("trace", req.trace_id)
        _events.emit(kind, rid=rid, eng=self._eng_id, **attrs)

    def _pool_args(self) -> tuple:
        """``(pools, fresh)`` of a tick dispatch (shared by the unified
        and spec sites): the device state and the pages whose scales
        the tick resets first (None without scales). Order matters:
        ``take_fresh`` runs BEFORE ``pools`` is captured — its overflow
        path eagerly rewrites the scales, and capturing first would
        dispatch the stale arrays and then clobber the reset with the
        tick's output."""
        fresh = self.pool.take_fresh(self._fresh_cap)
        return (self.pool.pools, fresh)

    def _first_call(self, site: str, fn, args: tuple):
        """Remember a dispatch site's argument avals (shape/dtype only
        — captured BEFORE dispatch, since donation invalidates the pool
        buffers) the first time it dispatches. Returns what that dispatch
        runs under: phase ``setup/first_call`` [``site``] the first time
        (recompile.py's listener charges the compilation's seconds to
        the site), nothing after."""
        if site in self._program_args:
            return _NOTHING

        def aval(a):
            if hasattr(a, "shape") and hasattr(a, "dtype"):
                return jax.ShapeDtypeStruct(np.shape(a), a.dtype)
            x = np.asarray(a)
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        self._program_args[site] = (
            fn, jax.tree_util.tree_map(aval, args))
        return _ptrace.phase(_recompile.FIRST_CALL, site=site)

    def _run_tick(self, args: tuple):
        """Dispatch the tick (unified, or the spec engine's verify tick).
        Its first dispatch compiles it, and says once how the pools
        travel through the compiled program: the gauges
        ``serving/tick_temp_bytes`` and ``serving/tick_alias_bytes``
        (``memory_analysis()``). Updated in place the tick's temporaries
        are far under one pool and it aliases all of the donated pools;
        a pool-sized temporary is a whole-pool copy every tick (ROADMAP
        S3; chip_smoke.py fails on it). Tracing, lowering and compiling
        still happen once: the call finds what ``lower`` made, and
        ``compile`` finds the call's executable. All of it is inside
        the site's ``setup/first_call`` phase."""
        if self._tick_site in self._program_args:
            with _quiet_donation():
                return self._tick(*args)
        with _quiet_donation(), \
                self._first_call(self._tick_site, self._tick, args):
            lowered = self._tick.lower(*args)
            out = self._tick(*args)
            memory = lowered.compile().memory_analysis()
        if memory is not None:
            reg = _registry()
            reg.gauge("serving/tick_temp_bytes").set(
                float(memory.temp_size_in_bytes))
            reg.gauge("serving/tick_alias_bytes").set(
                float(memory.alias_size_in_bytes))
        return out

    def record_program_stats(self) -> Dict[str, dict]:
        """Fold compile wall-time + ``cost_analysis()`` FLOPs/bytes of
        every hot-path program that has dispatched at least once into
        the profiler's program inventory (``xla_stats``), keyed by
        ``compiled_sites`` names. Re-lowers from the captured avals and
        compiles OFF the hot path (a diagnostic compile, suppressed in
        retrace telemetry; on a warm XLA cache it times the cache hit).
        Returns {site: stats-dict}."""
        from ..profiler import xla_stats as _xla

        out = {}
        for site, (fn, avals) in sorted(self._program_args.items()):
            out[site] = _xla.record_lowered(
                site, fn.lower(*avals)).to_dict()
        return out

    @contextmanager
    def trace_window(self, log_dir: Optional[str] = None,
                     peak_flops: Optional[float] = None):
        """Capture a parsed device-trace window over the ticks driven
        inside the block (ISSUE 11)::

            with eng.trace_window() as cap:
                for _ in range(8):
                    eng.step()
                eng.drain(0)          # sync before the trace stops
            cap.summary               # per-tick device timeline

        Records the hot-path programs first (``record_program_stats``
        — registers the HLO-module -> site join keys and cost-analysis
        FLOPs, so slices attribute to ``serving.tick#N`` and the MFU
        ledger has its numerator), then wraps the block in a
        ``device_trace.capture`` whose ``steps`` is set to the MEASURED
        tick count (the ``serving/ticks`` counter delta), so the
        summary's per-step rows read per-tick. Callers must drain
        in-flight ticks before the block ends or the trailing device
        work is cut off the timeline."""
        from ..profiler import device_trace as _dtrace

        self.record_program_stats()
        t0 = _registry().counter("serving/ticks").value
        cap = _dtrace.capture(log_dir=log_dir, peak_flops=peak_flops,
                              label=f"serving.eng{self._eng_id}")
        with cap:
            yield cap
            cap.steps = int(
                _registry().counter("serving/ticks").value - t0) or None

    def latency_stats(self, window_s: Optional[float] = None) -> dict:
        """Rolling-window TTFT/TPOT p50/p90/p95/p99 over requests
        finished in the last ``window_s`` seconds (None: everything
        still in the event ring). Reads the process-global event log —
        finished requests of OTHER live engines are included; use
        ``profiler.latency_table()`` rows (grouped by ``eng``) to
        split."""
        return _events.request_latency_stats(window_s=window_s)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int,
               key: Optional[np.ndarray] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               hold_after_prefill: bool = False,
               trace_id: Optional[str] = None,
               due_t: Optional[float] = None) -> int:
        """Queue one request. ``temperature``/``top_k``/``top_p``
        override the engine-global sampling params for this request
        only (ignored under greedy decode). Returns its request id.
        ``trace_id`` (ISSUE 14) tags every event of this request with
        a cross-host ``trace`` attr and rides any KV handoff.
        ``due_t`` (``time.perf_counter`` clock): when the request was
        due, for a driver whose loop submits late; ``serving/ttft_ms``
        then runs from it, and the ``submit`` event says how late
        (``late_ms``).

        Nothing here touches the serving device. The default ``key``
        (``fold_in(PRNGKey(config.seed), rid)``, whatever the decode
        mode: a hand-off to a sampling engine carries it) is made on
        the host's CPU backend. Folded on the serving device it queued
        behind every tick in flight, and the loop that calls
        ``submit()`` between steps stood still for three ticks
        (ISSUE 25). ``serving/submit_ms`` has this call's host time.

        ``hold_after_prefill`` puts the request in prefill-group mode
        (ISSUE 13): the engine prefills the prompt (chunked, prefix-
        cached, preemptible — all the normal machinery) and samples the
        FIRST token, then parks the slot instead of decoding; the
        coordinator exports the KV pages (``export_held``) to a decode
        engine and releases the slot (``release_exported``). Held slots
        never ride decode ticks, so a prefill-group engine's tick only
        ever carries chunk rows."""
        began = time.perf_counter()
        if hold_after_prefill:
            self.pool.require("handoff",
                              "hold_after_prefill (a prefill group)")
        p = np.asarray(prompt_ids, np.int32).reshape(-1)
        t0 = p.shape[0]
        cap = self.pool.slot_capacity
        if t0 < 1 or max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and max_new >= 1")
        if t0 + max_new_tokens - 1 > cap:
            raise ValueError(
                f"prompt {t0} + {max_new_tokens} new tokens needs "
                f"{t0 + max_new_tokens - 1} cache positions; slot capacity "
                f"is {cap} (pages_per_slot * page_size) — raise "
                "pages_per_slot or page_size")
        if self.pool.pages_for(t0 + max_new_tokens - 1) > \
                self.pool.allocator.num_pages - 1:
            raise ValueError("request exceeds the whole page pool")
        rid = self._next_rid
        self._next_rid += 1
        if key is None:
            with _ptrace.scope("submit/fold_key"):
                key = np.asarray(_fold_key(self._base_key, np.uint32(rid)))
        now = time.perf_counter()
        req = Request(rid=rid, prompt=p, max_new=int(max_new_tokens),
                      key=np.asarray(key, np.uint32),
                      submit_t=now, due_t=due_t, queue_t=now,
                      orig_prompt_len=t0,
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      hold=bool(hold_after_prefill),
                      trace_id=trace_id)
        self._requests[rid] = req
        self._queue.append(req)
        # the prefix-hit-rate denominator (ISSUE 16 mesh rollup:
        # prefix_hit_tokens / prompt_tokens)
        _registry().counter("serving/prompt_tokens").add(t0)
        late = {} if due_t is None else \
            {"late_ms": round((now - due_t) * 1e3, 3)}
        self._emit("submit", rid, prompt_tokens=t0,
                   max_new=int(max_new_tokens), **late)
        _registry().histogram("serving/submit_ms").observe(
            (time.perf_counter() - began) * 1000.0)
        return rid

    def step(self) -> bool:
        """One scheduler iteration: bound the in-flight window, admit
        into free slots, select up to ``prefill_chunks_per_tick``
        prompt chunks, grow pages (preempting on exhaustion), dispatch
        ONE unified tick carrying the selected chunks plus every
        resident decode. Returns whether any device work was
        dispatched."""
        n = self._tick_no
        log = self._ticks
        log.enter(n)
        self._sched.on_tick()
        self._drain(self.config.max_inflight)
        # host phases of the tick about to be dispatched, on the
        # profiler's clock (profiler/trace.py: ``pt:step/*``); the tick
        # log reads the clock as each closes
        with _ptrace.scope("step/admit", tick=n):
            self._admit()
        log.mark(_ticklog.ADMIT)
        with _ptrace.scope("step/chunks", tick=n):
            chunks = self._collect_chunks()
        log.mark(_ticklog.CHUNKS)
        with _ptrace.scope("step/grow", tick=n):
            self._grow_pages()
        log.mark(_ticklog.GROW)
        if self._spec is not None:
            dispatched = self._dispatch_spec(chunks)
            log.mark(_ticklog.DISPATCH)
        else:
            dispatched = self._dispatch_unified(chunks)
        reg = _registry()
        active = sum(r is not None for r in self._slot_rid)
        reg.gauge("serving/queue_depth").set(float(len(self._queue)))
        reg.gauge("serving/active_slots").set(float(active))
        reg.gauge("serving/page_util").set(self.pool.allocator.utilization())
        log.leave(bool(active or self._queue))
        return dispatched

    def tick_log(self) -> _ticklog.TickLog:
        """This engine's record of every tick (profiler/ticklog.py): the
        rows ``profiler.tick_logs()[engine id]`` finds."""
        return self._ticks

    def run(self) -> Dict[int, np.ndarray]:
        """Drive until every submitted request finished; returns
        {rid: generated ids np.int32[<=max_new]}."""
        while True:
            progressed = self.step()
            if not progressed:
                if self._inflight:
                    self._drain(0)
                    continue
                if all(r is None for r in self._slot_rid):
                    if not self._queue:
                        break
                    # every slot free, window empty, still can't admit
                    raise RuntimeError(
                        "serving queue stalled: page pool too small for "
                        "the queued prompt")
                raise RuntimeError(
                    "serving scheduler deadlock: resident requests but "
                    "nothing dispatchable")
        return {rid: np.asarray(r.out, np.int32)
                for rid, r in self._requests.items()
                if r.done and not r.canceled}

    def drain(self, target: int = 0) -> None:
        """Materialize in-flight ticks until at most ``target`` remain."""
        self._drain(target)

    def idle(self) -> bool:
        """True when nothing is queued, resident, or in flight."""
        return (not self._queue and not self._inflight
                and all(r is None for r in self._slot_rid))

    def tokens_so_far(self, rid: int) -> Tuple[int, ...]:
        """The tokens request ``rid`` has been handed so far (those the
        host has drained), finished or not."""
        return tuple(self._requests[rid].out)

    def served_weights(self) -> Tuple[dict, dict]:
        """The weights as the tick reads them: ``(stacked, other)``, the
        model's ``_decode_state()`` (a GPT's block parameters stacked
        ``[L, ...]`` by suffix and the rest by name)."""
        return self._stacked, self._other

    def exit_steps(self, rid: Optional[int] = None) -> Tuple[float, float,
                                                             int]:
        """A looped model's exit statistics, from its record (``models/
        tick.LoopRecord``): (mean expected exit step, mean chosen exit
        step, tokens) of request ``rid`` or, with none given, of every row
        sampled since this was last read that way."""
        if self._loop_steps > 1:
            return self.tick_record.exit_steps(rid)
        return 0.0, 0.0, 0 if rid is None else len(self._requests[rid].out)

    def reset_results(self) -> None:
        """Forget finished requests (long-running host keeps memory flat)."""
        self._requests = {rid: r for rid, r in self._requests.items()
                          if not r.done}
        if self.tick_record is not None:
            self.tick_record.forget(self._requests)

    def cancel(self, rid: int, reason: str = "redispatch") -> bool:
        """Abandon a request wherever it stands — queued, resident
        (prefilling or decoding), or held-ready — freeing its slot and
        pages WITHOUT producing a result (ISSUE 17 orphan bookkeeping:
        when the mesh re-dispatches a gid away from this rank, the
        stale local work must be torn down or it would double-serve).
        Drains in-flight ticks first (a slot cannot be released under
        a tick that still carries its row), releases the slot/pages,
        marks the request done+canceled so the scheduler forgets it,
        and emits a ``cancel`` event. Returns False for an unknown or
        already-finished request (idempotent)."""
        req = self._requests.get(rid)
        if req is None or req.done:
            return False
        if any(r.rid == rid for r in self._queue):
            self._queue = type(self._queue)(
                r for r in self._queue if r.rid != rid)
        elif rid in self._slot_rid:
            # rare control-plane op: materializing the in-flight
            # window is the price of releasing a live slot safely
            self._drain(0)
            if rid in self._slot_rid:     # not finished by the drain
                slot = self._slot_rid.index(rid)
                self._spec_reset(slot)
                self._sched.note_release(slot)
                self.pool.release_slot(slot)
                self._slot_rid[slot] = None
                self._slot_len[slot] = 0
        if req.done:                      # the drain finished it for
            return False                  # real — a result exists
        self._held_ready.discard(rid)
        req.done = True
        req.canceled = True
        req.out = []
        _registry().counter("serving/requests_canceled").add(1)
        self._emit("cancel", rid, reason=reason)
        return True

    # ------------------------------------------------------------------
    # KV handoff (ISSUE 13, serving/disagg.py): a prefill-group engine
    # exports a held request's pages; a decode-group engine imports
    # them. Pages move as raw pool bytes, keyed by the pools' field
    # names (``Pools.arrays``) — int8 pools hand off int8 values +
    # their per-page scales, so the PR 12 byte cut applies to the
    # transfer for free. The import writer is a jitted fixed-shape
    # maintenance op like the COW copy (self._copy): it is NOT a
    # hot-path dispatch site, so ``compiled_sites`` is unchanged and
    # the decode group's tick stays the one tick it was.
    # ------------------------------------------------------------------
    def held_ready(self) -> Tuple[int, ...]:
        """rids submitted with ``hold_after_prefill`` whose prompt is
        fully prefilled and first token materialized — exportable."""
        return tuple(sorted(self._held_ready))

    def export_held(self, rid: int) -> dict:
        """The KV-handoff payload of a held-ready request: its current
        prompt, remaining budget, sampling state, first token, and the
        raw page content (+ scales when quantized) for the
        ``ceil(t0 / page_size)`` pages holding the prompt's KV. The
        slot stays resident until ``release_exported`` — export is
        read-only, so a failed send can simply retry."""
        self.pool.require("handoff", "export_held (a KV handoff)")
        if rid not in self._held_ready:
            raise ValueError(f"request {rid} is not held-ready")
        t_span = time.perf_counter()
        req = self._requests[rid]
        slot = self._slot_rid.index(rid)
        pages = list(self.pool._held[slot])
        idx = np.asarray(pages, np.int32)
        t0 = int(self._slot_len[slot])
        assert t0 == req.prompt.shape[0], "held slot frontier != prompt"
        payload = {
            "prompt": np.asarray(req.prompt, np.int32),
            "orig_prompt_len": int(req.orig_prompt_len),
            "max_new": int(req.max_new),
            "first_token": int(req.out[0]),
            "key": np.asarray(req.key, np.uint32),
            "n_tokens": t0,
            "preempts": int(req.preempts),
            # the receiving pool must store the SAME representation —
            # int8 bytes dequantize only with their scales, and f32
            # bytes are garbage reinterpreted as int8
            "kv_dtype": str(np.dtype(self.pool.k.dtype)),
        }
        content = {name: np.asarray(a[:, idx])
                   for name, a in self.pool.pools.arrays().items()}
        payload.update(content)
        # per-request sampling overrides travel with the request (only
        # when set — absent keys mean "decode rank's engine defaults",
        # exactly like a local submit with None overrides)
        if req.temperature is not None:
            payload["temperature"] = float(req.temperature)
        if req.top_k is not None:
            payload["top_k"] = int(req.top_k)
        if req.top_p is not None:
            payload["top_p"] = float(req.top_p)
        if req.trace_id is not None:
            # the cross-host join key rides the payload: the decode
            # rank's request (and all its events) joins this trace
            payload["trace_id"] = req.trace_id
        nbytes = sum(a.nbytes for a in content.values())
        reg = _registry()
        reg.counter("serving/handoffs_out").add(1)
        reg.counter("serving/handoff_bytes_out").add(nbytes)
        self._emit("handoff_out", rid, slot=slot, tokens=t0,
                   pages=len(pages), bytes=nbytes,
                   ms=round((time.perf_counter() - t_span) * 1e3, 3))
        return payload

    def release_exported(self, rid: int) -> None:
        """Drop a held request after its payload shipped: publish the
        fully-written prompt pages into the local prefix index (an
        identical later prompt re-prefills for free — rank-local by
        design), release the slot, and mark the request done HERE (the
        decode group owns the visible finish)."""
        if rid not in self._held_ready:
            raise ValueError(f"request {rid} is not held-ready")
        req = self._requests[rid]
        slot = self._slot_rid.index(rid)
        self._insert_prefix(slot, req.prompt, int(self._slot_len[slot]))
        self._sched.note_release(slot)
        self.pool.release_slot(slot)
        self._slot_rid[slot] = None
        self._slot_len[slot] = 0
        self._held_ready.discard(rid)
        req.done = True

    def admit_prefilled(self, payload: dict) -> Optional[int]:
        """Decode-group admission of an exported payload: bind a free
        slot, allocate the prompt's pages, write the transferred KV
        (+ scales) into them, and seed the decode state exactly where a
        local prefill finisher would have left it (frontier at the
        prompt length, one token dispatched, ``last_tok`` = the first
        token) — so the next unified tick is an ordinary decode row and
        greedy output stays bitwise the single-host stream. Returns the
        local rid, or None when no slot/pages are free right now (the
        caller retries; imports never preempt residents — a transfer
        must not evict committed decode work)."""
        self.pool.require("handoff", "admit_prefilled (a KV handoff)")
        t_span = time.perf_counter()
        p = np.asarray(payload["prompt"], np.int32).reshape(-1)
        t0 = p.shape[0]
        max_new = int(payload["max_new"])
        first_tok = int(payload["first_token"])
        tid = payload.get("trace_id")
        tid = str(tid) if tid is not None else None
        src_dtype = payload.get("kv_dtype")
        if src_dtype is not None and \
                str(np.dtype(str(src_dtype))) != \
                str(np.dtype(self.pool.k.dtype)):
            raise ValueError(
                f"handoff payload carries {str(src_dtype)!r} KV pages "
                f"but this pool stores {np.dtype(self.pool.k.dtype)!s} "
                "— prefill and decode groups must serve the same "
                "kv_dtype (silently casting would corrupt the cache)")
        cap = self.pool.slot_capacity
        if t0 + max_new - 1 > cap:
            raise ValueError(
                f"handoff needs {t0 + max_new - 1} cache positions; "
                f"slot capacity is {cap}")
        free = [s for s, r in enumerate(self._slot_rid) if r is None]
        if not free:
            return None
        slot = free.pop()
        n_pages = self.pool.pages_for(t0)
        if n_pages != payload["k"].shape[1]:
            raise ValueError(
                f"payload carries {payload['k'].shape[1]} pages for a "
                f"{t0}-token prompt; expected {n_pages}")
        if not self.pool.grow_slot(slot, n_pages):
            return None
        rid = self._next_rid
        self._next_rid += 1
        now = time.perf_counter()
        req = Request(rid=rid, prompt=p, max_new=max_new,
                      key=np.asarray(payload["key"], np.uint32),
                      out=[first_tok], submit_t=now, queue_t=now,
                      orig_prompt_len=int(payload["orig_prompt_len"]),
                      preempts=int(payload.get("preempts", 0)),
                      trace_id=tid)
        req.first_token_t = now
        self._requests[rid] = req
        self._slot_rid[slot] = rid
        self._slot_len[slot] = t0
        self._slot_prompt[slot] = t0
        self._slot_dispatched[slot] = 1
        self._slot_looked_up[slot] = True     # no prefill owed here
        self._admit_seq += 1
        self._slot_admit_seq[slot] = self._admit_seq
        self._slot_admit_t[slot] = now
        self._slot_wait_due[slot] = False    # no chunk ever opens here
        self._spec_reset(slot)
        self._keys[slot] = req.key
        c = self.config
        self._temps[slot] = c.temperature if \
            payload.get("temperature") is None else payload["temperature"]
        self._topks[slot] = c.top_k if payload.get("top_k") is None \
            else payload["top_k"]
        self._topps[slot] = c.top_p if payload.get("top_p") is None \
            else payload["top_p"]
        self._write_pages(self.pool._held[slot], payload)
        self._last_tok = self._last_tok.at[slot].set(first_tok)
        nbytes = sum(payload[name].nbytes
                     for name in self.pool.pools.arrays())
        reg = _registry()
        reg.counter("serving/handoffs_in").add(1)
        reg.counter("serving/handoff_bytes_in").add(nbytes)
        self._emit("handoff_in", rid, slot=slot, tokens=t0,
                   pages=n_pages, bytes=nbytes,
                   ms=round((time.perf_counter() - t_span) * 1e3, 3))
        # the transferred first token may already satisfy the stop
        # conditions — finish without ever decoding
        eos = self.config.eos_token_id
        if eos is not None and first_tok == eos:
            self._finish(slot, rid, reason="eos")
        elif len(req.out) >= req.max_new:
            self._finish(slot, rid, reason="max_new")
        return rid

    def _write_pages(self, pages, payload: dict) -> None:
        """One fixed-shape jitted scatter (padded to ``pages_per_slot``
        with the null page, whose content is always masked and whose
        scale pad is 0 — the null-scale pin survives) so imports of any
        page count share one compiled program. ``pages`` is the
        explicit destination list: a slot's held pages for a request
        handoff, or freshly-allocated index pages for a migrated
        prefix chain (ISSUE 18) — both ride the SAME jitted writer."""
        pool = self.pool
        pps = pool.pages_per_slot
        n = len(pages)
        dst = np.zeros(pps, np.int32)
        dst[:n] = pages
        content = {}
        for name, a in pool.pools.arrays().items():
            buf = np.zeros((a.shape[0], pps) + a.shape[2:], a.dtype)
            buf[:, :n] = payload[name]
            content[name] = buf
        with _quiet_donation():
            pool.pools = self._import_fn(pool.pools, dst,
                                         Pools(**content))
        # scale rows the import just wrote: the next tick's fresh-page
        # reset must not zero them
        for pg in pages:
            pool.claim_fresh(int(pg))

    # ------------------------------------------------------------------
    # hot prefix-chain migration (ISSUE 18). Host-side policy on the
    # SAME handoff representation as export_held/admit_prefilled: raw
    # page content (+ scales when quantized), never re-derived — so a
    # request admitted onto a migrated chain stays bitwise the stream
    # it would have produced where the chain originated. The jitted
    # page writer is shared with the request-handoff import; no new
    # compiled site.
    # ------------------------------------------------------------------
    def export_prefix_chain(self, tokens) -> Optional[dict]:
        """Payload replicating this rank's cached prefix chain of
        ``tokens`` (full indexed pages only, capped at one slot's
        worth — longer can't be aliased into any slot anyway), or None
        when nothing is cached — the chain may have been evicted since
        it was published, and a missed migration is a perf event, not
        an error."""
        self.pool.require("handoff", "export_prefix_chain (prefix migration)")
        pool = self.pool
        if pool.prefix is None:
            return None
        toks = np.asarray(tokens, np.int32).reshape(-1)
        pages, _hashes = pool.prefix.chain_pages(toks)
        pages = pages[:pool.pages_per_slot]
        if not pages:
            return None
        n = len(pages)
        n_tok = n * pool.page_size
        # one fixed-shape jitted gather (padded to pages_per_slot,
        # pad rows sliced off on the host) — chains of EVERY length
        # share one compiled program, so the first mid-serving
        # migration never pays a compile (the mirror of _write_pages)
        src = np.zeros(pool.pages_per_slot, np.int32)
        src[:n] = pages
        payload = {
            "tokens": toks[:n_tok],
            "n_tokens": n_tok,
            "kv_dtype": str(np.dtype(pool.k.dtype)),
        }
        for name, a in self._export_fn(pool.pools, src).arrays().items():
            payload[name] = np.asarray(a)[:, :n]
        return payload

    def import_prefix_chain(self, payload: dict) -> int:
        """Insert a migrated prefix chain into this rank's own trie
        under the normal refcount/COW rules: allocate fresh pages,
        write the transferred content (+ scales), index them, then
        drop the import's temporary reference — a chunk the local trie
        already held keeps the FIRST tenant's page (the import's copy
        of it returns straight to the pool). Returns the tokens newly
        indexed (0 = pool full right now, or nothing new — both
        perf-only). Raises ValueError on a payload this pool must not
        store (dtype/shape mismatch)."""
        self.pool.require("handoff", "import_prefix_chain (prefix migration)")
        pool = self.pool
        if pool.prefix is None:
            return 0
        toks = np.asarray(payload["tokens"], np.int32).reshape(-1)
        src_dtype = payload.get("kv_dtype")
        if src_dtype is not None and \
                str(np.dtype(str(src_dtype))) != \
                str(np.dtype(pool.k.dtype)):
            raise ValueError(
                f"migrated chain carries {str(src_dtype)!r} pages but "
                f"this pool stores {np.dtype(pool.k.dtype)!s}")
        n_pages = int(payload["k"].shape[1])
        ps = pool.page_size
        if n_pages < 1 or n_pages > pool.pages_per_slot or \
                n_pages * ps != toks.shape[0] or \
                payload["k"].shape != payload["v"].shape:
            raise ValueError("inconsistent migrated chain payload")
        missing = [f for f in pool.pools.arrays() if f not in payload]
        if missing:
            raise ValueError(f"migrated chain without {missing}")
        # plain free-list alloc, deliberately NOT pool._alloc: a
        # speculative import must never evict committed local cache
        # entries to make room for itself
        pages = pool.allocator.alloc(n_pages)
        if pages is None:
            return 0                 # no room: drop
        self._write_pages(pages, payload)
        new = pool.prefix.insert(toks, pages)
        # drop the import's temporary refcount: newly-indexed pages
        # stay at 1 (index-held); duplicates of already-cached chunks
        # hit 0 and return to the pool (their scales re-queue for
        # reset via the allocator's on_zero hook)
        pool.allocator.free(pages)
        kept = [p for p in pages if pool.allocator.refcount(p) > 0]
        pool.migrated_pages.update(kept)
        return new * ps

    def _tokens_done(self) -> int:
        return sum(len(r.out) for r in self._requests.values())

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------
    def _drain(self, target: int) -> None:
        """Materialize in-flight ticks oldest-first until at most
        ``target`` remain. The ONLY place device data reaches the host."""
        log = self._ticks
        while len(self._inflight) > target:
            ent = self._inflight.popleft()
            # did the host get here before the device finished the tick?
            waited = not ent.tok.is_ready()
            log.drain_begin()
            with _ptrace.scope("step/drain", tick=ent.tick,
                               waited=int(waited)):
                toks = np.asarray(ent.tok)
                # the tick's arrival, on ``time.perf_counter``'s clock
                arrived_ns = log.drain_got(ent.row, ent.tick, waited)
                now = arrived_ns * 1e-9
                note = None
                if self.tick_record is not None:
                    note = self.tick_record.tick(
                        ent.aux, ent.positions, [m[2] for m in ent.meta])
                for idx, slot, rid in ent.meta:
                    req = self._requests[rid]
                    if req.done:
                        continue    # EOS discovered while in flight
                    tok = int(toks[idx])
                    req.out.append(tok)
                    if note is not None:
                        note(rid, idx)
                    _registry().counter("serving/tokens_generated").add(1)
                    if req.first_token_t is None:
                        req.first_token_t = now
                        _registry().histogram("serving/ttft_ms").observe(
                            (now - req.ttft_from) * 1000.0)
                        self._emit("first_token", rid, slot=slot)
                    if req.hold:
                        # prefill-group mode: the first token is the LAST
                        # thing this engine computes for the request — park
                        # it for export; eos/max_new stops are the decode
                        # group's business (export_held ships the token)
                        self._held_ready.add(rid)
                        continue
                    eos = self.config.eos_token_id
                    # max_new counts tokens wanted since the LAST (re)queue —
                    # preemption moved earlier output into the prompt and
                    # shrank max_new to the remainder
                    if eos is not None and tok == eos:
                        self._finish(slot, rid, reason="eos")
                    elif len(req.out) >= req.max_new:
                        self._finish(slot, rid, reason="max_new")
            log.drain_end()
            reg = _registry()
            reg.counter("serving/drain_waited" if waited
                        else "serving/drain_ready").add(1)
            reg.histogram("serving/tick_turnaround_ms").observe(
                (arrived_ns - ent.dispatch_ns) / 1e6)

    def _insert_prefix(self, slot: int, tokens: np.ndarray,
                       written: int) -> None:
        """Register ``slot``'s fully-written pages (KV for
        ``tokens[:written]``) in the prefix index."""
        if self.pool.prefix is None:
            return
        n_full = min(written, tokens.shape[0]) // self.pool.page_size
        if n_full:
            self.pool.prefix.insert(
                tokens[:n_full * self.pool.page_size],
                [int(p) for p in self.pool.tables[slot, :n_full]])

    def _spec_reset(self, slot: int) -> None:
        """Invalidate the slot's draft state (admission, finish,
        preemption): the next tenant's draft cache re-feeds from 0."""
        if self._spec is None:
            return
        self._draft.reset_slot(slot)
        if self._spec_pend is not None:
            # a chained draft tick built on this tenant's frontier is
            # meaningless for the next one
            self._spec_pend["valid"][slot] = False
        if self._spec_ctl is not None:
            self._spec_ctl.reset(slot)
        self._spec_started[slot] = False
        self._spec_verifying[slot] = False

    def _finish(self, slot: int, rid: int,
                reason: str = "max_new") -> None:
        req = self._requests[rid]
        req.done = True
        self._held_ready.discard(rid)
        if self._slot_rid[slot] == rid:
            self._spec_reset(slot)
            self._sched.note_release(slot)
            # cache the finished sequence's pages (prompt AND generated
            # full pages) before release: an identical follow-up
            # conversation prefix becomes a prefix hit
            seq = np.concatenate(
                [req.prompt, np.asarray(req.out, np.int32)])
            self._insert_prefix(slot, seq, int(self._slot_len[slot]))
            self.pool.release_slot(slot)
            self._slot_rid[slot] = None
            self._slot_len[slot] = 0
        # fold the preemption-era prefix back into the result
        extra = req.prompt[req.orig_prompt_len:]
        if extra.size:
            req.out = list(extra) + req.out
        _registry().counter("serving/requests_finished").add(1)
        now = time.perf_counter()
        tokens = len(req.out)
        ttft = tpot = None
        if req.first_token_t is not None:
            ttft = (req.first_token_t - req.submit_t) * 1000.0
            tpot = (now - req.first_token_t) * 1000.0 / max(tokens - 1, 1)
            # budget-shaping telemetry (sched.py): O(1) per finish
            self._sched.note_finish(ttft, tpot)
            # the SAME per-finish value the finish event carries, as a
            # mergeable sketch — the live plane's mesh TPOT percentiles
            # therefore agree with the offline merger's event-derived
            # ones up to the sketch's stated rel_err (ISSUE 16)
            _registry().histogram("serving/tpot_ms").observe(tpot)
        self._emit("finish", rid, tokens=tokens, reason=reason,
                   preempts=req.preempts,
                   ttft_ms=None if ttft is None else round(ttft, 3),
                   tpot_ms=None if tpot is None else round(tpot, 3))

    def _admit(self) -> None:
        """Move queued requests into free slots. Page allocation is
        deferred to the per-chunk prefill path (so the prefix lookup
        runs as late as possible — an identical prompt admitted a few
        ticks later sees the first tenant's pages already cached)."""
        free = [s for s, r in enumerate(self._slot_rid) if r is None]
        while self._queue and free:
            req = self._queue.popleft()
            slot = free.pop()
            self._slot_rid[slot] = req.rid
            self._slot_len[slot] = 0
            self._slot_prompt[slot] = req.prompt.shape[0]
            self._slot_dispatched[slot] = 0
            self._slot_looked_up[slot] = False
            self._spec_reset(slot)
            self._admit_seq += 1
            self._slot_admit_seq[slot] = self._admit_seq
            self._slot_admit_t[slot] = time.perf_counter()
            self._slot_wait_due[slot] = True
            self._sched.note_admit(slot)
            self._emit("admit", req.rid, slot=slot)
            self._keys[slot] = req.key
            c = self.config
            self._temps[slot] = (c.temperature if req.temperature is None
                                 else req.temperature)
            self._topks[slot] = c.top_k if req.top_k is None else req.top_k
            self._topps[slot] = c.top_p if req.top_p is None else req.top_p

    # ------------------------------------------------------------------
    # chunk selection + prefix cache
    # ------------------------------------------------------------------
    def _next_prefill_slot(self, pend: Dict[int, int]) -> Optional[int]:
        """The slot that opens the next prefill chunk, per the
        configured policy (``ServingConfig.scheduler``; sched.py).
        Under the default ``fifo`` this is the oldest-admitted pending
        slot — completing one request's prefill start-to-finish both
        minimizes its TTFT and publishes its pages before the next
        identical prompt looks them up; ``sjf``/``aged-sjf`` order by
        remaining prefill tokens (with deadline aging). ``pend``
        overlays chunk ends selected earlier in the same tick."""
        cands = []
        for s, rid in enumerate(self._slot_rid):
            if rid is None:
                continue
            frontier = pend.get(s, int(self._slot_len[s]))
            remaining = int(self._slot_prompt[s]) - frontier
            if remaining > 0:
                cands.append((s, int(self._slot_admit_seq[s]),
                              remaining))
        return self._sched.pick(cands)

    def _lookup_prefix(self, slot: int, req: Request) -> None:
        """Alias the longest cached page-aligned prefix of the prompt
        into ``slot`` (plus one copy-on-write page when the prompt
        diverges from a cached chunk mid-page) and start prefill at the
        first uncached position."""
        if self.pool.prefix is None:
            return
        full_pages, partial = self.pool.prefix.lookup(req.prompt)
        _registry().counter("serving/prefix_lookups").add(1)
        hit = 0
        remote = 0
        if full_pages:
            self.pool.share_into_slot(slot, full_pages)
            hit = len(full_pages) * self.pool.page_size
            if self.pool.migrated_pages:
                # cross-rank economy evidence (ISSUE 18): tokens served
                # off pages that arrived via chain migration — this
                # rank never prefilled them
                remote = sum(1 for p in full_pages
                             if p in self.pool.migrated_pages) \
                    * self.pool.page_size
        if partial is not None:
            src, lcp = partial
            # pin the donor page: the grow below may evict unreferenced
            # cached pages — src must not be reclaimed (or handed back
            # as the destination) mid-copy
            self.pool.allocator.share([src])
            try:
                if self.pool.grow_slot(slot, 1):
                    dst = self.pool.tables[slot,
                                           self.pool.slot_pages(slot) - 1]
                    first = _NOTHING if self._copy_called else \
                        _ptrace.phase(_recompile.FIRST_CALL,
                                      site=self._copy_site)
                    self._copy_called = True
                    with _quiet_donation(), first:
                        self.pool.pools = self._copy(
                            self.pool.pools, np.int32(src), np.int32(dst))
                    # scales travel with the page; un-list dst from the
                    # fresh reset or the next tick would zero them
                    self.pool.claim_fresh(int(dst))
                    hit += lcp
                    _registry().counter("cache_share/cow_copies").add(1)
                    self._emit("cow_copy", req.rid, slot=slot, tokens=lcp)
            finally:
                self.pool.allocator.free([src])
        self._slot_len[slot] = hit
        if hit:
            _registry().counter("serving/prefix_hit_tokens").add(hit)
            if remote:
                _registry().counter(
                    "serving/prefix_hit_tokens_remote").add(remote)
            self._emit("prefix_hit", req.rid, slot=slot, tokens=hit,
                       remote_tokens=remote)

    def _observe_wait(self, req: "Request") -> None:
        """One wait sample per admission cycle. Fresh admissions anchor
        at submit (scheduler delay); requeued victims anchor at their
        preemption (preemption cost) — folding both into one
        submit-anchored histogram conflated the two (ISSUE 8
        satellite). Called at the cycle's first chunk open, or from
        ``_preempt_for`` when a cycle is preempted before it ever
        opened one — so qw count == requests and rw count ==
        preemptions hold under every interleaving."""
        wait_ms = (time.perf_counter() - req.queue_t) * 1000.0
        name = "serving/requeue_wait_ms" if req.preempts \
            else "serving/prefill_queue_wait_ms"
        _registry().histogram(name).observe(wait_ms)

    def _open_chunk(self, s: int,
                    pend: Dict[int, int]) -> Optional[_Chunk]:
        """Run the slot's first-chunk prefix lookup if due, then size
        the next prompt chunk and acquire its pages. Returns the chunk
        descriptor, or None when the slot was freed along the way
        (finished in the drain, or became its own preemption victim)."""
        rid = self._slot_rid[s]
        req = self._requests[rid]
        if not self._slot_looked_up[s]:
            self._slot_looked_up[s] = True
            self._observe_wait(req)
            self._lookup_prefix(s, req)
        t0 = int(self._slot_prompt[s])
        start = pend.get(s, int(self._slot_len[s]))
        end = min(start + self.prefill_chunk, t0)
        need = self.pool.pages_for(end) - self.pool.slot_pages(s)
        if not self._acquire_pages(s, need):
            return None
        if self._slot_wait_due[s]:
            # admission -> FIRST chunk open, per admission cycle:
            # recorded only once the chunk actually opened (pages
            # acquired) — the direct evidence of what the selection
            # policy did to start-of-service latency (ISSUE 15);
            # cycles preempted before ever opening contribute none
            self._slot_wait_due[s] = False
            wait_ms = (time.perf_counter()
                       - self._slot_admit_t[s]) * 1000.0
            _registry().histogram("serving/chunk_wait_ms").observe(
                wait_ms)
            self.chunk_waits_ms.append(wait_ms)
        self._sched.note_open(s)
        return (s, rid, start, end, t0)

    def _collect_chunks(self) -> List[_Chunk]:
        """Select up to the policy's per-tick budget of prompt chunks
        and acquire their pages WITHOUT dispatching — the unified tick
        carries them as prefill rows. The budget is shaped by
        decode-stall telemetry (sched.py ``chunk_budget``) but capped
        at the compiled ``prefill_chunks_per_tick`` worst case, so the
        tick shape never retraces; fifo keeps the constant budget.
        ``_slot_len`` commits only at dispatch: page acquisition can
        preempt a slot whose chunk was already selected (the chunk is
        then dropped), and publishing a frontier the dropped chunk
        never wrote would poison the prefix index."""
        chunks: List[_Chunk] = []
        pend: Dict[int, int] = {}
        npf = self.config.prefill_chunks_per_tick
        budget = npf
        if self._sched.shape_budget:
            pending = sum(
                1 for s, rid in enumerate(self._slot_rid)
                if rid is not None
                and int(self._slot_len[s]) < self._slot_prompt[s])
            budget = min(npf, self._sched.chunk_budget(
                pending, len(self._ticking_slots()),
                len(self._queue)))
            if budget < npf and pending:
                _registry().counter("serving/budget_cuts").add(1)
        for _ in range(budget):
            s = self._next_prefill_slot(pend)
            if s is None:
                break
            chunk = self._open_chunk(s, pend)
            if chunk is None:
                # the selected slot was freed during page acquisition
                # (finished in the drain, or became its own preemption
                # victim) — it is no longer a candidate, so spend the
                # remaining budget on the next pick instead of
                # abandoning the tick's chunk service (the aged-sjf
                # starvation bound rests on pending slots getting at
                # least one open per tick whenever one CAN open)
                continue
            pend[s] = chunk[3]
            chunks.append(chunk)
        return chunks          # _dispatch_unified drops stale entries

    def _acquire_pages(self, s: int, need: int) -> bool:
        """Grow slot ``s`` by ``need`` pages, escalating: free list
        (+ prefix-cache LRU eviction inside ``grow_slot``) -> drain
        in-flight finishes -> preempt youngest-first. The ONE
        exhaustion-recovery path, shared by prefill chunks and decode
        growth. Returns False when ``s`` itself was freed along the way
        (finished in the drain, or became its own preemption victim);
        raises only in the can't-happen state where the pool cannot
        cover a request ``submit()`` already validated against it."""
        if need <= 0 or self.pool.grow_slot(s, need):
            return True
        # draft pages are strictly lower-value than target pages:
        # reclaim them (decayed slots first, then everyone) before
        # draining finishes or preempting a tenant (ISSUE 20)
        if self._reclaim_draft(all_slots=False) and \
                self.pool.grow_slot(s, need):
            return True
        self._drain(0)
        if self._slot_rid[s] is None:
            return False
        if self.pool.grow_slot(s, need):
            return True
        if self._reclaim_draft(all_slots=True) and \
                self.pool.grow_slot(s, need):
            return True
        if not any(x != s and self._slot_rid[x] is not None
                   for x in range(self.config.num_slots)):
            raise RuntimeError(
                "serving pool exhausted: cannot cover a resident "
                "request even with the prefix cache drained and no "
                "co-resident to preempt")
        self._preempt_for(s, need)
        return self._slot_rid[s] is not None

    def _reclaim_draft(self, all_slots: bool) -> int:
        """Return draft-KV pages to the pool under target-page
        pressure. ``all_slots=False`` releases only slots whose
        adaptive depth has decayed to 0 (they are not speculating
        anyway — this is the 'adaptive-k decay returns draft pages'
        arm); ``all_slots=True`` releases every draft cache (the slots
        fall back to plain decode and re-feed if pressure eases).
        Never touches target pages. Returns pages freed."""
        if self._spec is None:
            return 0
        freed = 0
        for s in range(self.config.num_slots):
            if self._draft.aux.slot_pages(s) == 0:
                continue
            decayed = (self._spec_ctl is not None
                       and self._spec_ctl.depth(s) == 0)
            if all_slots or decayed:
                freed += self._draft.release_pages(s)
                if self._spec_pend is not None:
                    self._spec_pend["valid"][s] = False
        if freed:
            _registry().counter(
                "serving/spec_draft_pages_reclaimed").add(freed)
        return freed

    # ------------------------------------------------------------------
    # decode scheduling
    # ------------------------------------------------------------------
    def _ticking_slots(self) -> List[int]:
        """Slots that should advance this tick: resident, prefill
        complete, not finished, and with emissions still owed. A slot
        whose final token is already dispatched stops ticking
        immediately (max-token stop is host-deterministic); EOS stops
        lag by <= max_inflight ticks."""
        out = []
        for s, rid in enumerate(self._slot_rid):
            if rid is None:
                continue
            req = self._requests[rid]
            if req.hold:
                continue    # held slots stop at the prefill finisher's
            if not req.done and \
                    1 <= self._slot_dispatched[s] < req.max_new:
                out.append(s)
        return out

    def _grow_pages(self) -> None:
        for s in self._ticking_slots():
            if self._slot_rid[s] is None:
                continue            # freed by an earlier drain/preempt
            need_page = int(self._slot_len[s]) // self.pool.page_size
            if need_page < self.pool.slot_pages(s):
                continue
            self._acquire_pages(s, 1)

    def _preempt_for(self, needy_slot: int, need: int) -> None:
        """Free ``need`` pages by requeueing the youngest resident
        request (its generated prefix becomes prompt, so no work is
        redone twice — and its fully-written pages go into the prefix
        index first, so the re-prefill is a prefix hit)."""
        live = [s for s in range(self.config.num_slots)
                if self._slot_rid[s] is not None]
        victim = max(live, key=lambda s: self._slot_admit_seq[s])
        rid = self._slot_rid[victim]
        req = self._requests[rid]
        # window was drained before preemption, so req.out is current
        self._emit("preempt", rid, slot=victim, generated=len(req.out))
        if not self._slot_looked_up[victim]:
            # this admission cycle never opened a chunk: its wait
            # sample ends here (by preemption, not prefill start) —
            # without it the cycle's bucket is silently short a sample
            self._observe_wait(req)
        req.prompt = np.concatenate(
            [req.prompt, np.asarray(req.out, np.int32)])
        req.max_new -= len(req.out)
        req.out = []
        req.preempts += 1
        # a held-ready victim loses its parked first token with the
        # preemption (it moved into the prompt); the requeued cycle
        # re-prefills and parks again
        self._held_ready.discard(rid)
        req.queue_t = time.perf_counter()
        self._insert_prefix(victim, req.prompt, int(self._slot_len[victim]))
        self._queue.appendleft(req)
        self._spec_reset(victim)
        self._sched.note_release(victim)
        self.pool.release_slot(victim)
        self._slot_rid[victim] = None
        self._slot_len[victim] = 0
        _registry().counter("serving/preemptions").add(1)
        self._emit("requeue", rid, prompt_tokens=int(req.prompt.shape[0]),
                   max_new=req.max_new)
        if victim != needy_slot and self._slot_rid[needy_slot] is not None:
            if not self.pool.grow_slot(needy_slot, need):
                self._preempt_for(needy_slot, need)

    # ------------------------------------------------------------------
    # unified dispatch: ONE program per scheduler step
    # ------------------------------------------------------------------
    def _dispatch_unified(self, chunks: List[_Chunk]) -> bool:
        """Assemble and dispatch the mixed-row tick: one decode row per
        slot (inactive slots write to the null page through their
        zeroed table rows) plus
        one ``prefill_chunk``-token row block per selected chunk. A
        chunk whose slot lost its request between selection and here
        (decode growth preempted it) is dropped — its acquired pages
        were already released with the slot."""
        chunks = [c for c in chunks if self._slot_rid[c[0]] == c[1]]
        ticking = self._ticking_slots()
        if not ticking and not chunks:
            return False
        log = self._ticks
        with _ptrace.scope("step/build", tick=self._tick_no):
            args, finishers = self._build_unified(chunks, ticking)
        log.mark(_ticklog.BUILD)
        # does the device have anything left to run? The last tick's output
        # says so for chunk-only ticks too, which ``_inflight`` never holds
        starved = self._last_tok.is_ready()
        with _ptrace.scope("step/dispatch", tick=self._tick_no):
            self.pool.pools, tok, self._last_tok, aux = \
                self._run_tick(args)
        dispatch_ns = log.mark(_ticklog.DISPATCH)
        row = log.tick(self._tick_no, len(ticking),
                       sum(c[3] - c[2] for c in chunks), int(starved))
        meta = [(s, s, self._slot_rid[s]) for s in ticking]
        meta += [(s, s, rid) for s, rid in finishers]
        if meta:
            # chunk-only ticks (no decodes, no finishers) emit nothing
            # worth syncing — queueing them would stall the host on a
            # token vector nobody reads once the window fills
            # where each emitting row's query stood: a decode row at its
            # slot's length, a finished prompt's at its last token
            positions = self._slot_len.copy()
            for s, _, _, end, t0 in chunks:
                if end >= t0:
                    positions[s] = t0 - 1
            self._inflight.append(_Inflight(
                tok, meta, self._tick_no, aux, positions, row, dispatch_ns))
        self._tick_no += 1
        self.max_inflight_seen = max(self.max_inflight_seen,
                                     len(self._inflight))
        for s in ticking:
            self._slot_len[s] += 1
            self._slot_dispatched[s] += 1
        for s, rid, start, end, t0 in chunks:
            self._slot_len[s] = end
            self._emit("chunk", rid, slot=s, start=start, end=end,
                       final=bool(end >= t0))
            if end >= t0:
                self._slot_dispatched[s] = 1
                _registry().counter("serving/prefills").add(1)
            # publish the pages this chunk completed (progressively: a
            # long shared prompt becomes hittable page-by-page)
            self._insert_prefix(s, self._requests[rid].prompt, end)
        reg = _registry()
        reg.counter("serving/ticks").add(1)
        if not chunks:                  # what the tick was told: has_chunks
            reg.counter("serving/ticks_without_chunk").add(1)
        # pages of windowed layers that no later query can see go back now:
        # every tick that reads them is already dispatched
        reg.counter("serving/window_pages_freed").add(sum(
            self.pool.free_behind(s, int(self._slot_len[s]))
            for s in set(ticking) | {c[0] for c in chunks}))
        for kind, share in self.pool.live_shares().items():
            reg.gauge("serving/live_pages{pool=%s}" % kind).set(share)
        if self._loop_steps > 1:
            reg.counter("loop/steps_run").add(self._loop_steps)
        if chunks:
            reg.counter("serving/prefill_chunks").add(len(chunks))
        reg.gauge("serving/mixed_rows").set(float(len(ticking)
                                                  + len(chunks)))
        reg.gauge("serving/mixed_rows_decode").set(float(len(ticking)))
        reg.gauge("serving/mixed_rows_prefill").set(float(len(chunks)))
        return True

    def _build_unified(self, chunks: List[_Chunk],
                       ticking: List[int]) -> tuple:
        """The mixed-row tick's arguments (numpy row metadata) and the
        ``(slot, rid)`` pairs whose prompt this tick finishes."""
        ns = self.config.num_slots
        w = self.prefill_chunk
        npf = self.config.prefill_chunks_per_tick
        nps = self.pool.pages_per_slot
        cap = self.pool.slot_capacity
        nt = ns + npf * w
        pf_toks = np.zeros(npf * w, np.int32)
        tok_pos = np.zeros(nt, np.int32)
        tok_limit = np.zeros(nt, np.int32)   # pad rows: limit 0 -> null page
        tok_pos[:ns] = self._slot_len
        tok_limit[:ns] = cap
        # ragged row metadata: ns decode rows, then npf chunk rows (pad
        # chunk rows keep an all-null table)
        rows = list(range(ns)) + [c[0] for c in chunks] \
            + [None] * (npf - len(chunks))
        row_tab = self.pool.row_tables(rows)
        row_pos0 = np.zeros(ns + npf, np.int32)
        row_pos0[:ns] = self._slot_len
        row_len = np.ones(ns + npf, np.int32)
        row_len[ns:] = 0          # a pad chunk row: nothing to fetch
        sample_ix = np.zeros(ns, np.int32)
        sample_pos = np.zeros(ns, np.int32)
        emit = np.zeros(ns, bool)
        for s in ticking:
            sample_ix[s] = s
            sample_pos[s] = self._slot_len[s] + 1
            emit[s] = True
        finishers = []
        for c, (s, rid, start, end, t0) in enumerate(chunks):
            base = ns + c * w
            req = self._requests[rid]
            pf_toks[c * w:c * w + (end - start)] = req.prompt[start:end]
            tok_pos[base:base + w] = start + np.arange(w)
            tok_limit[base:base + w] = t0
            row_pos0[ns + c] = start
            row_len[ns + c] = end - start
            # the slot's decode row must sit at the post-chunk frontier
            # (it garbage-writes there, overwritten by the next real
            # token — never at a position this tick's chunk covers)
            tok_pos[s] = end
            row_pos0[s] = end
            if end >= t0:
                finishers.append((s, rid))
                sample_ix[s] = base + (t0 - 1 - start)
                sample_pos[s] = t0
                emit[s] = True
        # blocks the attention kernel's loops visit this tick over blocks
        # at capacity
        _registry().gauge("serving/attn_live_block_share").set(
            live_block_share(row_pos0, row_len, self.pool.page_size, nps))
        tail = (self._last_tok, pf_toks, tok_pos, tok_limit, row_tab,
                row_pos0, row_len, sample_ix, sample_pos, emit,
                np.bool_(len(chunks) > 0),
                np.ascontiguousarray(self._keys),
                np.ascontiguousarray(self._temps),
                np.ascontiguousarray(self._topks),
                np.ascontiguousarray(self._topps))
        return ((self._stacked, self._other) + self._pool_args() + tail,
                finishers)

    def _make_unified_tick(self):
        """The ONE compiled hot-path program: every resident decode and
        every selected prefill chunk of a scheduler step, as ragged
        rows of a single forward, the model's ``ragged_apply``. All metadata is
        fixed-shape (pad prefill rows ride with limit 0), so the
        program traces exactly once across any prefill/decode mix,
        admission order, or per-request sampling params, and is one
        body for all of them: the pools are never a ``cond``'s operand
        or result here (ROADMAP S3). Decode token
        values come from the DEVICE-side ``last_tok`` (the deferred
        sync never materializes them on the host); the final chunk of
        a prompt emits its slot's first token via ``sample_ix``, and
        ``emit`` folds emitted tokens back into ``last_tok`` for the
        next tick."""
        site = self._tick_site
        apply = self._apply
        ns = self.config.num_slots
        w = self.prefill_chunk

        def tick(stacked, other, pools, fresh, last_tok, pf_toks,
                 tok_pos, tok_limit, row_tab, row_pos0, row_len,
                 sample_ix, sample_pos, emit, has_chunks, keys, temps,
                 top_ks, top_ps):
            _recompile.mark_trace(site, jax.tree_util.tree_leaves(pools)[0],
                                  row_tab, tok_pos, last_tok)
            # recycled pages restart their running-max scale at 0
            # (fresh pads with the null page, whose scale is 0)
            pools = pools.reset_scales(fresh)
            tokens = jnp.concatenate([last_tok, pf_toks])
            # ONE body for every mix. The pools are updated in place as
            # the carry of the forward's layer scan (ROADMAP S3); a
            # ``cond`` that took and returned them cost two whole-pool
            # copies a tick, on both branches, to save a tick without a
            # chunk 1 ms. On such a tick the chunk rows ride as the pad
            # rows the program already knows (``tok_limit`` 0: their
            # writes land on the null page; all-null tables), and
            # ``has_chunks`` only lets the block skip their attention,
            # which reads the pools and returns ``[nch, w, NH, D]``
            # (``gpt_ragged_apply``), or a forward of unlike layers run
            # its row-wise stretches between two calls on the pools over
            # the decode rows alone (Falcon-H1, Olmo-Hybrid:
            # ``models/tick.TickRows.dense``; dots3, DeepSeek-V2 and Ling
            # ignore it): neither ``cond`` takes or returns the pools.
            # what the forward says of itself (``aux``: a looped model's
            # exit steps, a latent model's statistics) leaves the tick as
            # outputs beside the tokens, no callback; of no entries, none
            logits, pools, aux = apply(
                stacked, other, pools, tokens, tok_pos, tok_limit,
                row_tab, row_pos0, row_len, sample_ix, decode_rows=ns,
                chunk_width=w, has_chunks=has_chunks)
            with _ptrace.annotate("tick/sample"):
                nxt = self._sample_tok(logits, keys, sample_pos, temps,
                                       top_ks, top_ps)
                new_last = jnp.where(emit, nxt, last_tok)
            return pools, nxt, new_last, aux

        return tick

    # ------------------------------------------------------------------
    # speculative decoding (ServingConfig.spec; serving/spec.py): the
    # draft tick runs k tokens ahead per caught-up slot, then ONE
    # verify/mixed tick scores every slot's (1+k)-token row through
    # the same ragged program that carries the prefill chunks. Host
    # syncs each verify tick (acceptance decides the next tick's
    # positions); emitted tokens are always the TARGET's argmax
    # stream, so greedy output is bitwise non-speculative greedy.
    # ------------------------------------------------------------------
    def _dispatch_spec(self, chunks: List[_Chunk]) -> bool:
        """One spec scheduler step: (1) draft tick — parallel
        catch-up feed for behind slots + k draft steps for caught-up
        decoding slots (greedy argmax, or the slot's own sampling law
        under ``decode='sampling'``); slots with a valid CHAINED draft
        (overlap mode) skip this tick — their drafts were built by the
        previous step's chained dispatch; (2) per-slot speculation
        depth ``k_s`` (clamped by remaining budget, target page
        headroom AND draft page headroom — best-effort growth only,
        never preempting a co-resident to speculate deeper); (3) the
        verify/mixed tick (greedy longest-argmax-prefix acceptance, or
        the rejection-sampling kernel); (4) in overlap mode, dispatch
        the NEXT draft tick chained on the verify tick's still-on-
        device outputs — the host sync below then hides under its
        execution; (5) absorb — append the accepted prefix +
        correction token, rewind both frontiers past the rejected tail
        and return their pages, reconcile the chained tick's validity
        against what actually absorbed."""
        chunks = [c for c in chunks if self._slot_rid[c[0]] == c[1]]
        ticking = self._ticking_slots()
        if not ticking and not chunks:
            return False
        ns = self.config.num_slots
        k = self._spec_k
        w = self.prefill_chunk
        npf = self.config.prefill_chunks_per_tick
        nps = self.pool.pages_per_slot
        cap = self.pool.slot_capacity
        dr = self._draft
        reg = _registry()
        ticking_set = set(ticking)
        self._spec_tick_depth.clear()   # fresh probe decisions per tick
        sampling = self._spec_sampling
        pend = self._spec_pend

        # ---- draft tick: feed + generate (catch-up dispatch) ----
        feed_toks = np.zeros((ns, w), np.int32)
        feed_pos0 = np.zeros(ns, np.int32)
        feed_len = np.zeros(ns, np.int32)
        gen_tok = np.zeros(ns, np.int32)
        gen_pos = np.full(ns, cap, np.int32)   # cap = null-routed
        last_tok = np.zeros(ns, np.int32)
        gen_slots: List[int] = []
        chained: List[int] = []   # slots riding the pending chained tick
        any_feed = False
        for s, rid in enumerate(self._slot_rid):
            if rid is None:
                continue
            req = self._requests[rid]
            if s in ticking_set:
                last_tok[s] = req.out[-1]
            pend_ok = pend is not None and bool(pend["valid"][s])
            if self._spec_ctl is not None:
                # one probe-state advance per slot per tick (ISSUE 16
                # re-probe); the ks clamp below reuses the cached value
                self._spec_tick_depth[s] = \
                    self._spec_ctl.tick_depth(s)
                if self._spec_tick_depth[s] == 0:
                    # adaptive depth decayed to 0 (ISSUE 15): the slot
                    # rides as a plain decode row — feeding/drafting a
                    # cache nobody will verify is pure draft-tick cost,
                    # so the slot drops out of the draft tick entirely
                    # (a tick with nothing to feed and nobody
                    # generating skips the draft dispatch altogether,
                    # converging the engine to plain-engine cost
                    # structure). Reset on the next admission cycle —
                    # or a scheduled re-probe (SpecConfig.
                    # reprobe_every) — re-enables it.
                    if pend_ok:
                        pend["valid"][s] = False
                    continue
            if pend_ok:
                if s in ticking_set and \
                        req.max_new - len(req.out) >= 2:
                    # the chained draft tick already seeded past this
                    # frontier and drafted k tokens — no feed, no
                    # re-generate (the overlap payoff)
                    chained.append(s)
                    continue
                pend["valid"][s] = False
            behind = int(self._slot_len[s]) - int(dr.len[s])
            fed = 0
            if behind > 0:
                # catch the draft cache up toward the accepted
                # frontier: prompt tokens (admission / prefix hits the
                # draft never saw) and emitted tokens ride the same
                # chunk-shaped feed
                fed = min(behind, w)
                lo = int(dr.len[s])
                if not dr.grow_for(s, lo + fed):
                    # draft pages are best-effort: feed only as far as
                    # the pages already held reach
                    fed = max(0, min(fed, dr.held_tokens(s) - lo))
                if fed:
                    seq = np.concatenate(
                        [req.prompt, np.asarray(req.out, np.int32)])
                    feed_toks[s, :fed] = seq[lo:lo + fed]
                    feed_pos0[s] = lo
                    feed_len[s] = fed
                    any_feed = True
                    if not self._spec_started[s]:
                        self._spec_started[s] = True
                        self._emit("draft", rid, slot=s, pos=lo)
            if s in ticking_set and behind - fed == 0 and \
                    req.max_new - len(req.out) >= 2 and \
                    dr.grow_for(s, min(int(self._slot_len[s]) + k,
                                       cap)):
                gen_tok[s] = req.out[-1]
                gen_pos[s] = int(self._slot_len[s])
                gen_slots.append(s)
        draft_flat = self._zero_drafts
        dprobs_m = self._zero_probs if sampling else None
        drafts = dprobs = None
        if any_feed or gen_slots:
            dtab = np.ascontiguousarray(dr.aux.tables)
            zi = np.zeros(ns, np.int32)
            # no chain rides a catch-up tick: a zero chain, all masked
            dsample = (np.ascontiguousarray(self._keys),
                       np.ascontiguousarray(self._temps),
                       np.ascontiguousarray(self._topks),
                       np.ascontiguousarray(self._topps),
                       np.zeros((ns, 1 + k), np.int32), zi, zi,
                       np.zeros(ns, bool)) if sampling else None
            dargs = (dr.stacked, dr.other, dr.kc, dr.vc, dtab,
                     feed_toks, feed_pos0, feed_len, gen_tok, gen_pos,
                     dsample, np.bool_(any_feed),
                     np.bool_(len(gen_slots) > 0))
            with _quiet_donation(), \
                    self._first_call(dr.site, dr.tick, dargs):
                dr.kc, dr.vc, drafts, *probs = dr.tick(*dargs)
            if sampling:
                dprobs = dprobs_m = probs[0]
            draft_flat = drafts.reshape(-1)
            dr.len += feed_len
            reg.counter("serving/spec_draft_ticks").add(1)
            if any_feed:
                reg.counter("serving/spec_feed_tokens").add(
                    int(feed_len.sum()))
        if chained:
            # splice the pending chained drafts (device arrays from the
            # previous step's overlapped dispatch) over this tick's
            cm = np.zeros(ns, bool)
            cm[chained] = True
            cmj = jnp.asarray(cm)
            base_d = drafts if drafts is not None \
                else jnp.zeros((ns, k), jnp.int32)
            base_p = dprobs if dprobs is not None else self._zero_probs
            drafts = jnp.where(cmj[:, None], pend["drafts"], base_d)
            dprobs_m = jnp.where(cmj[:, None, None], pend["probs"],
                                 base_p)
            draft_flat = drafts.reshape(-1)
            reg.counter("serving/spec_chained_consumed").add(
                len(chained))

        # ---- per-slot speculation depth (host-deterministic) ----
        k_arr = np.zeros(ns, np.int32)
        for s in gen_slots + chained:
            rid = self._slot_rid[s]
            req = self._requests[rid]
            pos0 = int(self._slot_len[s])
            ks = min(k, req.max_new - len(req.out) - 1, cap - 1 - pos0)
            if self._spec_ctl is not None:
                # adaptive depth (ISSUE 15): the slot's accept-rate
                # EWMA picks a depth in the compiled [0, k] range —
                # a decayed slot rides as a plain decode row. The
                # cached tick_depth keeps a re-probe tick at depth 1
                # consistent between the feed loop and this clamp.
                ks = min(ks, self._spec_tick_depth.get(
                    s, self._spec_ctl.depth(s)))
            if ks <= 0:
                continue
            need = self.pool.pages_for(pos0 + ks + 1) \
                - self.pool.slot_pages(s)
            if need > 0 and not self.pool.grow_slot(s, need):
                # pool pressure: speculate only as deep as the pages
                # already held reach (k_s may hit 0 = plain decode row)
                ks = min(ks, self.pool.slot_pages(s)
                         * self.pool.page_size - pos0 - 1)
            if ks > 0:
                k_arr[s] = ks
                if not self._spec_verifying[s]:
                    self._spec_verifying[s] = True
                    self._emit("verify", rid, slot=s, k=ks)
        has_drafts = bool(k_arr.any())

        # ---- assemble + dispatch the verify/mixed tick ----
        base = ns * (1 + k)
        nt = base + npf * w
        pf_toks = np.zeros(npf * w, np.int32)
        tok_pos = np.zeros(nt, np.int32)
        tok_limit = np.zeros(nt, np.int32)
        tok_pos[:ns] = self._slot_len
        tok_limit[:ns] = cap
        dj = np.arange(k)[None, :]
        tok_pos[ns:base] = (self._slot_len[:, None] + 1 + dj) \
            .astype(np.int32).reshape(-1)
        tok_limit[ns:base] = np.where(dj < k_arr[:, None], cap, 0) \
            .astype(np.int32).reshape(-1)
        rows = list(range(ns)) + [c[0] for c in chunks] \
            + [None] * (npf - len(chunks))
        row_tab = self.pool.row_tables(rows)
        row_pos0 = np.zeros(ns + npf, np.int32)
        row_pos0[:ns] = self._slot_len
        row_len = np.ones(ns + npf, np.int32)
        row_len[:ns] += k_arr
        sample = np.zeros((ns, 1 + k), np.int32)
        sample[:, 0] = np.arange(ns)
        sample[:, 1:] = ns + np.arange(ns)[:, None] * k \
            + np.arange(k)[None, :]
        # per-row emission positions for the sampling law: a ticking
        # slot's primary token folds at slot_len + 1 (same as the
        # unified tick); a prefill finisher's at t0 (set below)
        sample_pos = (self._slot_len + 1).astype(np.int32)
        finishers = []
        for c, (s, rid, start, end, t0) in enumerate(chunks):
            coff = base + c * w
            req = self._requests[rid]
            pf_toks[c * w:c * w + (end - start)] = req.prompt[start:end]
            tok_pos[coff:coff + w] = start + np.arange(w)
            tok_limit[coff:coff + w] = t0
            row_pos0[ns + c] = start
            row_len[ns + c] = end - start
            tok_pos[s] = end
            row_pos0[s] = end
            if end >= t0:
                finishers.append((s, rid))
                sample[s, 0] = coff + (t0 - 1 - start)
                sample_pos[s] = t0
        vsample = (np.ascontiguousarray(self._keys), sample_pos,
                   np.ascontiguousarray(self._temps),
                   np.ascontiguousarray(self._topks),
                   np.ascontiguousarray(self._topps),
                   dprobs_m) if sampling else None
        tail = (last_tok, draft_flat, pf_toks, tok_pos, tok_limit,
                row_tab, row_pos0, row_len, sample.reshape(-1), k_arr,
                vsample, np.bool_(len(chunks) > 0), np.bool_(has_drafts))
        args = (self._stacked, self._other) + self._pool_args() + tail
        # the tick log's clock; the inline sync below lands in the row's
        # ``dispatch`` part, and the row carries no arrival (a speculative
        # engine's holds are named, not sized)
        dispatch_ns = self._ticks.clock()
        self.pool.pools, tok_m, acc = self._run_tick(args)

        # ---- overlap: chain draft tick N+1 on the un-materialized
        # verify outputs, BEFORE the host sync below — the sync then
        # hides under this dispatch's execution (ISSUE 20 tentpole) ----
        pend_new = None
        if sampling and self._spec_overlap and has_drafts:
            cm2 = np.zeros(ns, bool)
            ch_pos0 = np.zeros(ns, np.int32)
            for s in np.nonzero(k_arr)[0]:
                s = int(s)
                req = self._requests[self._slot_rid[s]]
                pos0 = int(self._slot_len[s])
                ks = int(k_arr[s])
                # the chained scan writes draft positions up to
                # pos0 + acc + k <= pos0 + ks + k; chain only when the
                # draft pages cover the worst case (best-effort — a
                # refusal just means a catch-up tick next step)
                if req.max_new - len(req.out) < 2 or \
                        not dr.grow_for(s, min(pos0 + ks + k + 1,
                                               cap)):
                    continue
                cm2[s] = True
                ch_pos0[s] = pos0
            if cm2.any():
                dtab2 = np.ascontiguousarray(dr.aux.tables)
                zi2 = np.zeros(ns, np.int32)
                dargs2 = (dr.stacked, dr.other, dr.kc, dr.vc, dtab2,
                          np.zeros((ns, w), np.int32), zi2, zi2, zi2,
                          np.full(ns, cap, np.int32),
                          (np.ascontiguousarray(self._keys),
                           np.ascontiguousarray(self._temps),
                           np.ascontiguousarray(self._topks),
                           np.ascontiguousarray(self._topps),
                           tok_m, acc, ch_pos0, cm2),
                          np.bool_(False), np.bool_(True))
                with _quiet_donation(), \
                        self._first_call(dr.site, dr.tick, dargs2):
                    dr.kc, dr.vc, ch_drafts, ch_probs = \
                        dr.tick(*dargs2)
                pend_new = {"drafts": ch_drafts, "probs": ch_probs,
                            "valid": cm2, "pos0": ch_pos0}
                reg.counter("serving/spec_draft_ticks").add(1)
                reg.counter("serving/spec_chained_ticks").add(1)
        # the previous pend was consumed (or invalidated) above; the
        # new one must be installed before the absorb loop so _finish/
        # _spec_reset/_reclaim_draft invalidate the RIGHT entries
        self._spec_pend = pend_new

        # ---- chunk bookkeeping (same as the unified tick) ----
        for s, rid, start, end, t0 in chunks:
            self._slot_len[s] = end
            self._emit("chunk", rid, slot=s, start=start, end=end,
                       final=bool(end >= t0))
            if end >= t0:
                reg.counter("serving/prefills").add(1)
            self._insert_prefix(s, self._requests[rid].prompt, end)

        # ---- synchronous absorb: acceptance, rewind, finishes ----
        toks = np.asarray(tok_m)                       # [ns, 1+k]
        accs = np.asarray(acc)
        arrived_ns = self._ticks.clock()
        now = arrived_ns * 1e-9
        reg.counter("serving/drain_waited").add(1)      # an inline sync
        reg.histogram("serving/tick_turnaround_ms").observe(
            (arrived_ns - dispatch_ns) / 1e6)
        eos = self.config.eos_token_id
        for s, rid in [(t, self._slot_rid[t]) for t in ticking] \
                + finishers:
            req = self._requests[rid]
            ks = int(k_arr[s])
            a = min(int(accs[s]), ks) if ks else 0
            pos0 = int(self._slot_len[s])
            emitted = 0
            finished = None
            for j in range(a + 1):
                tok = int(toks[s, j])
                req.out.append(tok)
                emitted += 1
                reg.counter("serving/tokens_generated").add(1)
                if req.first_token_t is None:
                    req.first_token_t = now
                    reg.histogram("serving/ttft_ms").observe(
                        (now - req.ttft_from) * 1000.0)
                    self._emit("first_token", rid, slot=s)
                if eos is not None and tok == eos:
                    finished = "eos"
                    break
                if len(req.out) >= req.max_new:
                    finished = "max_new"
                    break
            if s in ticking_set:
                # the accepted prefix's KV is in the cache (written by
                # this verify row); the rejected tail is truncated off
                self._slot_len[s] = pos0 + emitted
                if ks:
                    gained = emitted - 1
                    reg.counter("serving/spec_drafted_tokens").add(ks)
                    reg.counter("serving/spec_accepted_tokens").add(
                        gained)
                    reg.histogram("serving/spec_accept_len").observe(
                        float(gained))
                    if self._spec_ctl is not None:
                        self._spec_ctl.observe(s, gained, ks)
                    self._emit("accept", rid, slot=s, accepted=gained,
                               drafted=ks)
                if s in gen_slots or s in chained:
                    # reconcile the chained tick against what actually
                    # absorbed: the chain's on-device seed assumed the
                    # full accepted prefix + correction was emitted and
                    # the slot kept ticking — anything else (EOS inside
                    # the window, max_new stop) invalidates it and the
                    # slot falls back to a catch-up tick
                    chain_ok = (pend_new is not None
                                and bool(pend_new["valid"][s])
                                and finished is None
                                and emitted == a + 1
                                and len(req.out) < req.max_new)
                    if chain_ok:
                        # the chained tick wrote the seed at the new
                        # frontier (and healed the full-accept hole):
                        # the draft cache is already caught up
                        dr.len[s] = pos0 + emitted
                    else:
                        if pend_new is not None:
                            pend_new["valid"][s] = False
                        # the draft's own speculation wrote the
                        # accepted tokens' KV — its frontier follows
                        # without repair; pages past it go back to the
                        # pool (the draft-side rewind)
                        dr.rewind(s, pos0 + min(emitted, k))
                if finished is None and ks:
                    # rewind: return pages past the new frontier (+1
                    # page headroom for the next tick's write) — the
                    # refcount machinery keeps shared pages alive
                    self.pool.shrink_slot(
                        s, self.pool.pages_for(
                            int(self._slot_len[s]) + 1))
            self._slot_dispatched[s] = len(req.out)
            if finished is not None:
                self._finish(s, rid, reason=finished)
        self._ticks.tick(self._tick_no, len(ticking),
                         sum(c[3] - c[2] for c in chunks), -1)
        self._tick_no += 1
        reg.counter("serving/ticks").add(1)
        if chunks:
            reg.counter("serving/prefill_chunks").add(len(chunks))
        reg.gauge("serving/mixed_rows").set(
            float(len(ticking) + len(chunks)))
        reg.gauge("serving/mixed_rows_decode").set(float(len(ticking)))
        reg.gauge("serving/mixed_rows_prefill").set(float(len(chunks)))
        reg.gauge("serving/spec_rows").set(float(int((k_arr > 0).sum())))
        # mean OFFERED draft depth across speculating slots this tick
        # (0.0 when nobody speculated): under adaptive k this is the
        # live evidence of convergence — full depth at high accept,
        # decaying toward 0 as drafts keep getting rejected
        reg.gauge("serving/spec_k_effective").set(
            float(k_arr[k_arr > 0].mean()) if (k_arr > 0).any()
            else 0.0)
        drafted = reg.counter("serving/spec_drafted_tokens").value
        if drafted:
            reg.gauge("serving/spec_accept_rate").set(
                reg.counter("serving/spec_accepted_tokens").value
                / drafted)
        # the draft cache's footprint in the SHARED pool (ISSUE 20):
        # pages held by draft tables / pages allocated overall — the
        # residency ledger prices draft and target bytes together
        dp = dr.aux.total_pages()
        reg.gauge("serving/draft_pool_pages").set(float(dp))
        share = dp / max(self.pool.allocator.num_allocated, 1)
        reg.gauge("serving/draft_pool_share").set(share)
        # peak survives the end-of-run release (slots return their
        # draft pages on finish, so the plain gauge reads 0 by the
        # time a bench harness snapshots the registry)
        reg.gauge("serving/draft_pool_share_peak").set_max(share)
        return True

    # ------------------------------------------------------------------
    # compiled program bodies
    # ------------------------------------------------------------------
    def _sample_tok(self, logits, keys, positions, temps, top_ks, top_ps):
        """Token choice from last-token logits [N, V]. Greedy mirrors
        ops/decoding.greedy_decode (argmax of f32 log_softmax — parity);
        sampling applies the PER-ROW temperature/top-k/top-p arrays and
        folds each slot's key by the ABSOLUTE position of the emitted
        token, so a request's stream is independent of scheduling,
        preemption, and its neighbours' sampling params."""
        if self.config.decode == "greedy":
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return jnp.argmax(lp, axis=-1).astype(jnp.int32)
        from ..ops.decoding import apply_top_k_top_p_per_row

        lg = logits.astype(jnp.float32) / \
            jnp.maximum(temps, 1e-6)[:, None]
        lg = apply_top_k_top_p_per_row(lg, top_ks, top_ps)
        lp = jax.nn.log_softmax(lg, axis=-1)

        def one(key, pos, row):
            return jax.random.categorical(jax.random.fold_in(key, pos), row)

        return jax.vmap(one)(keys, positions, lp).astype(jnp.int32)
