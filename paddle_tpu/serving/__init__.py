"""paddle_tpu.serving — continuous-batching decode runtime on a paged
KV cache with prefix sharing.

The serving-side answer to the ROADMAP's "heavy traffic from millions
of users": instead of one dense-cache ``generate()`` program per
request batch, a fixed pool of KV **pages** (``paged_cache.py``) plus
ONE fixed-shape jitted **mixed-row tick** over cache slots
(``engine.py``) lets requests join and leave mid-decode — admission
fills slots as evictions free them, pages return to the pool the
moment their LAST holder lets go (the allocator refcounts pages), and
the host overlaps scheduling with device execution via the PR-3
deferred-sync idiom. Prompt prefixes are **shared**: fully-written
prompt pages live in a hash-trie index (``PrefixCache``) and admission
aliases the longest cached page-aligned prefix instead of recomputing
it; prefill of the remaining suffix is **chunked** (Sarathi-style —
bounded work per scheduler step) and the chunks ride the SAME tick as
resident decodes, as ragged rows of one
``ops/paged_attention.ragged_paged_attention`` call per layer
("Ragged Paged Attention": per-row ``(pos0, true_len)`` metadata; a
decode row is simply ``true_len == 1``), in the spelling the platform
picks where the tick is traced: the Pallas ragged kernel on a TPU, the
XLA gather anywhere else. What the engine asks of a model is three
methods (``models/tick.py``). Speculative
decoding (``ServingConfig.spec`` = ``SpecConfig(draft_model, k)``,
``spec.py``) amortizes the target over k drafted tokens per verify
tick with greedy acceptance — spec greedy output stays BITWISE equal
to plain greedy (the classic invariant, tested). Every POLICY
decision is pluggable and host-side (``sched.py``, ISSUE 15):
``ServingConfig.scheduler`` picks the chunk-selection order (fifo /
sjf / aged-sjf with a provable starvation bound), non-fifo policies
shape the per-tick prefill budget from decode-stall telemetry,
``SpecConfig.adaptive`` drives per-slot draft depth from an
accept-rate EWMA, and disagg routing balances on estimated
time-to-first-chunk — all without touching a compiled program.

Quick use::

    from paddle_tpu.serving import ServingEngine, ServingConfig
    eng = ServingEngine(gpt_model, ServingConfig(num_slots=8,
                                                 page_size=16))
    rids = [eng.submit(prompt, max_new_tokens=64) for prompt in prompts]
    outputs = eng.run()               # {rid: np.int32 ids}

or, per request batch with the familiar surface::

    ids, _ = gpt_model.generate(tokens, max_new_tokens=64, paged=True)

The process needs the CPU backend beside the accelerator
(``JAX_PLATFORMS`` unset, or ``tpu,cpu``): ``submit()`` folds a
request's default sampling key on the process's own CPU device, so that
a request never queues behind the ticks in flight on the serving device
(ISSUE 25). Without it the engine's constructor raises and names the
setting.

Profiler integration (``paddle_tpu.profiler``): ``engine.py``'s docstring
lists every gauge, counter, histogram and event. The ONE compiled
hot-path site (``serving.tick#N``) must stay at ONE trace —
``ServingEngine.compiled_sites`` + the recompile registry make any
regression assertable (tests do).
"""
from __future__ import annotations

from .disagg import (DisaggServer, HandoffChannel, MeshSpec,  # noqa: F401
                     route_requests)
from .engine import Request, ServingConfig, ServingEngine  # noqa: F401
from .paged_cache import (NULL_PAGE, PageAllocator, PagePool,  # noqa: F401
                          Pools, PrefixCache)
from .sched import (SCHED_POLICIES, ChunkScheduler,  # noqa: F401
                    SpecKController)
from .spec import DraftRunner, SpecConfig  # noqa: F401

__all__ = ["ServingEngine", "ServingConfig", "Request", "SpecConfig",
           "DraftRunner", "PagePool", "Pools", "PageAllocator", "PrefixCache",
           "NULL_PAGE", "DisaggServer", "MeshSpec", "HandoffChannel",
           "route_requests", "SCHED_POLICIES", "ChunkScheduler",
           "SpecKController"]
