"""Abstract (lazy) parameter initialization — ``paddle.LazyGuard``.

Reference parity: Paddle's LazyGuard (python/paddle lazy init for
billion-parameter models whose eager init would not fit host RAM).
TPU-native translation: under the guard, ``build_parameter`` creates
Parameters whose ``_value`` is a ``jax.ShapeDtypeStruct`` — pure
metadata, zero bytes materialized. A lazily-built model can be:

  * AOT-lowered/compiled through the hybrid trainer
    (``HybridPipelineTrainer(..., abstract)`` detects the struct values
    and plans shardings + optimizer state abstractly) — this is how the
    GPT-3 13B memory plan (benchmarks/plan_13b.py, BENCH_13B_PLAN.json)
    compiles a 52 GB-state model on a laptop-sized host;
  * materialized later with ``materialize(model)`` (per-tensor init on
    demand, e.g. after sharding decisions are known).
"""
from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp

from ..profiler import trace as _ptrace

_state = threading.local()


def in_lazy_mode() -> bool:
    return getattr(_state, "lazy", False)


class LazyGuard:
    """Context manager: parameters created inside are abstract."""

    def __enter__(self):
        self._prev = getattr(_state, "lazy", False)
        _state.lazy = True
        return self

    def __exit__(self, *exc):
        _state.lazy = self._prev
        return False


def is_abstract(t) -> bool:
    """True if a Tensor (or raw value) is a LazyGuard metadata-only
    placeholder."""
    v = getattr(t, "_value", t)
    return isinstance(v, jax.ShapeDtypeStruct)


def materialize(layer, key=None):
    """Initialize every abstract parameter of ``layer`` for real, using
    each Parameter's recorded initializer (stashed by build_parameter)."""
    for _, p in layer.named_parameters():
        if p is not None and is_abstract(p):
            init = getattr(p, "_lazy_initializer", None)
            spec = p._value
            t0 = time.perf_counter()
            if init is None:
                p._value = jnp.zeros(spec.shape, spec.dtype)
            else:
                p._value = jnp.asarray(
                    init(list(spec.shape), spec.dtype), spec.dtype)
            _ptrace.charge_setup("weights", time.perf_counter() - t0,
                                 p._value.nbytes, where="host")
    return layer
