"""Instrumentation helpers shared by the trainer/pipeline hooks.

- collective accounting: parse a lowered (StableHLO) program for
  cross-device collectives and sum the bytes they move — the number that
  makes an allreduce-compression experiment (EQuARX-style) attributable
  instead of inferred from wall-clock deltas;
- device memory high-water marks via ``Device.memory_stats()`` (absent on
  the CPU backend — callers get None there);
- batch token counting for throughput metrics.
"""
from __future__ import annotations

import re
import time
from typing import Optional

import jax
import numpy as np

from .metrics import registry

# StableHLO collective ops (jax lowers psum/all_gather/ppermute/... to
# these). The text form is `%x = "stablehlo.all_reduce"(...)` or
# `stablehlo.all_reduce(...)` depending on printer version.
_COLLECTIVE_RE = re.compile(
    r"stablehlo\.(all_reduce|all_gather|all_to_all|collective_permute|"
    r"reduce_scatter|collective_broadcast)")
_TENSOR_RE = re.compile(r"tensor<([0-9x]*)x?([a-z][a-z0-9]+)>")
# everything after the function-type arrow: the op's result type(s)
_ARROW_RE = re.compile(r"->\s*(.*)$")
# post-partitioning HLO spelling (`compiled.as_text()`): the op name is
# dash-separated and the RESULT type(s) sit between `=` and the op name,
# e.g. `%ar = f32[8,4]{1,0} all-reduce(...)` or a `(f32[..], ...)` tuple.
# Async pairs: count the `-done` op (its result is the payload) and skip
# `-start` (its result tuple aliases operand+result — double the bytes).
_HLO_COLLECTIVE_RE = re.compile(
    r"=\s*(\(?[a-z][a-z0-9]+\[[^=]*?)\s"
    r"(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast)(?:-done)?\(")
_HLO_TYPE_RE = re.compile(r"([a-z][a-z0-9]+)\[([0-9,]*)\]")
# a tuple of more than five results prints `/*index=5*/` every fifth one:
# XLA's combiner makes one such all-reduce of a GSPMD step's gradients, and
# the `=` inside the comment must not end the result type
_HLO_COMMENT_RE = re.compile(r"/\*.*?\*/")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i1": 1, "pred": 1,
    # compiled-HLO spellings (`compiled.as_text()` prints s8/u32/...;
    # StableHLO prints i8/ui32/...). Without these an int8 collective's
    # payload (quantized AllReduce, qcomm.py) would fall through to the
    # 4-byte default and be counted as if it were still f32.
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1,
}

#: canonical spelling per dtype family, so byte breakdowns key the same
#: whether parsed from StableHLO (i8) or compiled HLO (s8)
_DTYPE_CANON = {"s8": "i8", "u8": "ui8", "s16": "i16", "u16": "ui16",
                "s32": "i32", "u32": "ui32", "s64": "i64", "u64": "ui64",
                "pred": "i1"}


def _tensor_bytes(dims: str, dtype: str) -> int:
    n = 1
    if dims:
        for d in dims.split("x"):
            if d:
                n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_stats(lowered_text: str) -> dict:
    """Count collectives and the bytes they move in a lowered program.

    ``lowered_text``: ``jitted.lower(...).as_text()`` (StableHLO) or
    ``.lower(...).compile().as_text()`` (optimized HLO). Bytes are the
    per-invocation result-buffer sizes — i.e. what one execution of the
    program moves across the collective, not link-level wire bytes
    (which depend on the algorithm XLA picks). NOTE: a GSPMD program
    (jit + shardings, no shard_map) keeps its collectives implicit until
    XLA's SPMD partitioner runs, so its StableHLO reports 0 — pass the
    COMPILED text to count those. Returns
    {"ops": {op_name: count}, "bytes": {op_name: bytes},
    "bytes_by_dtype": {canonical_dtype: bytes},
    "bytes_by_kind_dtype": {op_name: {canonical_dtype: bytes}},
    "total_bytes"} — the per-dtype split is what makes a
    quantized-collective experiment (distributed/qcomm.py) readable
    straight off the gauges instead of derived from op-level deltas,
    and the per-kind×per-dtype split is what separates the ring's two
    halves (reduce-scatter vs all-gather) for the ZeRO ledger.
    """
    ops: dict = {}
    byts: dict = {}
    by_dtype: dict = {}
    by_kind_dtype: dict = {}

    def _acc(op: str, dims: str, dtype: str) -> None:
        b = _tensor_bytes(dims, dtype)
        byts[op] = byts.get(op, 0) + b
        canon = _DTYPE_CANON.get(dtype, dtype)
        by_dtype[canon] = by_dtype.get(canon, 0) + b
        kd = by_kind_dtype.setdefault(op, {})
        kd[canon] = kd.get(canon, 0) + b

    lines = lowered_text.splitlines()
    i = 0
    while i < len(lines):
        line = lines[i]
        m = _COLLECTIVE_RE.search(line)
        if not m:
            hm = _HLO_COLLECTIVE_RE.search(_HLO_COMMENT_RE.sub("", line))
            if hm:
                op = hm.group(2).replace("-", "_")
                ops[op] = ops.get(op, 0) + 1
                for dt, dims in _HLO_TYPE_RE.findall(hm.group(1)):
                    _acc(op, dims.replace(",", "x"), dt)
            i += 1
            continue
        op = m.group(1)
        ops[op] = ops.get(op, 0) + 1
        # Region-bearing collectives (all_reduce / reduce_scatter carry
        # their reduction computation as a region) print the function
        # type on the region's CLOSING `}) : (...) -> ...` line; reading
        # the op line instead would pick up the replica_groups attribute
        # type (tensor<NxMxi64>).
        type_line = line
        if line.rstrip().endswith("({"):
            j = i + 1
            while j < len(lines):
                if lines[j].lstrip().startswith("})"):
                    type_line = lines[j]
                    i = j
                    break
                j += 1
        am = _ARROW_RE.search(type_line)
        tensors = _TENSOR_RE.findall(am.group(1)) if am else []
        if tensors:
            # after `->`: the result type(s); variadic collectives print
            # a tuple `(tensor<..>, tensor<..>)` — sum every buffer
            for d, t in tensors:
                _acc(op, d, t)
        else:
            # compact printer form has no arrow (`... applies stablehlo.add
            # : tensor<..>`): last tensor type on the line is the result
            tensors = _TENSOR_RE.findall(type_line)
            if tensors:
                dims, dt = tensors[-1]
                _acc(op, dims, dt)
        i += 1
    return {"ops": ops, "bytes": byts, "bytes_by_dtype": by_dtype,
            "bytes_by_kind_dtype": by_kind_dtype,
            "total_bytes": sum(byts.values())}


#: The ring's two halves, as gauge buckets over lowered op kinds. The
#: manual ring's reduce-scatter half lowers to ``collective_permute``
#: hops (ppermute) while GSPMD's spelling is a real ``reduce_scatter``
#: op — both are grad-sharding traffic, so they share the bucket.
#: ``all_reduce`` is deliberately in NEITHER: it is the fused
#: both-halves op, so a replicated AllReduce program reads 0 on both
#: half-gauges and the split stays strictly "ring halves".
_KIND_BUCKETS = {
    "reduce_scatter": ("reduce_scatter", "collective_permute"),
    "all_gather": ("all_gather",),
}
#: gauge-suffix -> canonical parsed dtypes folded into it
_DTYPE_BUCKETS = {"int8": ("i8", "ui8"), "bf16": ("bf16",),
                  "f32": ("f32",)}


def record_collective_stats(lowered_text: str, prefix: str = "comm") -> dict:
    """collective_stats + fold the totals into the metrics registry.

    Besides the blended total, the per-dtype gauges
    ``{prefix}/collective_bytes_int8`` / ``_f32`` make the "collective
    bytes halved" claim of a quantized-AllReduce config (qcomm.py)
    readable straight off the gauge: int8 counts the i8/ui8 payloads,
    f32 the f32 ones (block scales included — they ARE f32 wire
    bytes). The per-kind×per-dtype gauges
    ``{prefix}/collective_bytes_{reduce_scatter,all_gather}_{int8,
    bf16,f32}`` additionally split the ring's two halves (ZeRO's grad
    sharding vs param return, ISSUE 19) so "the sharded arm moved its
    gradient bytes over reduce-scatter" is a registry read, not an HLO
    diff."""
    st = collective_stats(lowered_text)
    reg = registry()
    reg.gauge(f"{prefix}/collective_bytes_per_step").set(st["total_bytes"])
    reg.gauge(f"{prefix}/collective_ops_per_step").set(
        sum(st["ops"].values()))
    bd = st["bytes_by_dtype"]
    reg.gauge(f"{prefix}/collective_bytes_int8").set(
        bd.get("i8", 0) + bd.get("ui8", 0))
    reg.gauge(f"{prefix}/collective_bytes_f32").set(bd.get("f32", 0))
    bkd = st["bytes_by_kind_dtype"]
    for kind, opnames in _KIND_BUCKETS.items():
        for sfx, canons in _DTYPE_BUCKETS.items():
            total = sum(bkd.get(op, {}).get(c, 0)
                        for op in opnames for c in canons)
            reg.gauge(
                f"{prefix}/collective_bytes_{kind}_{sfx}").set(total)
    return st


def record_collectives_from(lowered, mesh=None, prefix: str = "comm") -> dict:
    """record_collective_stats over a ``jax.stages.Lowered``, with the
    GSPMD fallback: when the StableHLO shows ZERO collectives on a
    multi-device mesh, parse the partitioned (compiled) program instead
    — GSPMD keeps its collectives implicit until XLA's SPMD partitioner,
    and only paying the extra compile in that case keeps shard_map
    programs cheap. (A mixed shard_map+GSPMD program whose StableHLO
    already shows some collectives skips the fallback and undercounts
    the implicit ones — callers wanting exact mixed accounting must pass
    compiled text to record_collective_stats themselves.)"""
    text = lowered.as_text()
    if not collective_stats(text)["ops"] and mesh is not None \
            and mesh.devices.size > 1:
        text = lowered.compile().as_text()
    return record_collective_stats(text, prefix)


def device_stamp() -> dict:
    """The device as jax reports it — what every result a benchmark or
    smoke run prints is stamped with."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_memory_stats(device=None) -> Optional[dict]:
    """``Device.memory_stats()`` of the first (or given) local device;
    None where the backend does not report (the CPU backend)."""
    stats = (device or jax.local_devices()[0]).memory_stats()
    if not stats:
        return None
    return dict(stats)


def record_memory_high_water(prefix: str = "memory") -> Optional[int]:
    """Record the device-memory high-water mark (bytes) as a max-gauge.
    Returns the current peak or None when the backend has no stats."""
    st = device_memory_stats()
    if st is None:
        return None
    peak = st.get("peak_bytes_in_use", st.get("bytes_in_use"))
    if peak is None:
        return None
    reg = registry()
    reg.gauge(f"{prefix}/peak_bytes_in_use").set_max(int(peak))
    if "bytes_in_use" in st:
        reg.gauge(f"{prefix}/bytes_in_use").set(int(st["bytes_in_use"]))
    return int(peak)


def _per_rank_bytes(v) -> int:
    """Per-rank resident bytes of one ledger entry: a pytree of arrays
    (each counted at its PER-DEVICE shard shape via
    ``sharding.shard_shape`` — a dp-sharded ZeRO slab counts 1/dp of
    its global size, a replicated param counts in full) or a plain int
    (pre-computed bytes, e.g. a transient gradient buffer that never
    materializes as a persistent array)."""
    if isinstance(v, (int, float)) and not hasattr(v, "shape"):
        return int(v)
    total = 0
    for a in jax.tree_util.tree_leaves(v):
        shape = getattr(a, "shape", ())
        sharding = getattr(a, "sharding", None)
        if sharding is not None:
            try:
                shape = sharding.shard_shape(tuple(shape))
            except Exception:
                pass
        n = 1
        for d in shape:
            n *= int(d)
        total += n * int(getattr(getattr(a, "dtype", None), "itemsize",
                                 None) or np.dtype(
                                     getattr(a, "dtype", "float32")
                                 ).itemsize)
    return total


def record_memory_ledger(categories: dict, prefix: str = "mem") -> dict:
    """The ZeRO memory ledger (ISSUE 19): per-rank resident bytes per
    state category, computed from ACTUAL array shardings — not a
    model. ``categories`` maps a name (``param`` / ``grad`` /
    ``opt_state`` / ``master``...) to a pytree of arrays or a raw byte
    count; each is folded into the ``{prefix}/{name}_bytes`` gauge
    (and thus ``profiler.summary()``, the Prometheus sink, and bench
    blocks). Returns ``{name: bytes}``. This is the gauge pair that
    states the ZeRO claim: sharded ``opt_state_bytes`` ≈ 1/dp of the
    replicated baseline's."""
    reg = registry()
    out = {}
    for name, v in categories.items():
        b = _per_rank_bytes(v)
        out[name] = b
        reg.gauge(f"{prefix}/{name}_bytes").set(b)
    return out


# Nominal interconnect bandwidth (bytes/s, per direction) used by the
# comm-phase MODEL below. v5e ICI is ~45 GB/s/link; the CPU figure is a
# loopback placeholder so the model degrades to ~0 on test platforms.
_LINK_BW = {"tpu": 45e9, "cpu": 10e9}


def estimate_comm_ms(total_bytes: int, platform: str = "tpu") -> float:
    """Lower-bound comm-phase time from collective bytes over the nominal
    interconnect bandwidth. A MODEL, not a measurement: XLA overlaps
    collectives with compute and picks algorithms that change wire bytes;
    this answers "how long would the bytes alone take at link rate" —
    0 for a program with no collectives (single chip)."""
    return total_bytes / _LINK_BW.get(platform, _LINK_BW["tpu"]) * 1e3


def _first_leaf(o) -> float:
    return float(np.asarray(jax.tree_util.tree_leaves(o)[0]).ravel()[0])


def time_compiled(fn, iters: int = 2) -> float:
    """Mean seconds per call of ``fn`` (a thunk running a jitted
    program): one call to compile + warm, then ``iters`` timed calls
    ended by a host fetch of the first output leaf — the only truthful
    sync point under async dispatch. Shared by every
    ``profile_step_phases`` so the phase numbers trainers report stay
    comparable."""
    _first_leaf(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    _first_leaf(out)
    return (time.perf_counter() - t0) / iters


def record_phases(fwd_s=None, fwdbwd_s=None, step_s=None,
                  comm_bytes=None, platform: str = "tpu",
                  cost_bytes_accessed=None) -> dict:
    """Fold a phase decomposition (seconds; any may be None) into the
    ``phase/*_ms`` gauges the profiler summary reports.

    The step is ONE fused XLA program, so trainers time nested prefixes
    (fwd-only, fwd+bwd, full step) and this derives
    bwd = fwdbwd − fwd, optim = step − fwdbwd. Returns the phases dict
    (ms).

    The comm phase is an honest two-number split, not one blended guess:

    - ``comm_ms`` — the nominal-bandwidth MODEL (estimate_comm_ms):
      collective bytes over link rate, ignoring overlap. Kept for
      continuity and as a lower bound on the unoverlapped cost.
    - ``comm_measured_ms`` — measured step wall time apportioned by
      XLA's own byte accounting (``cost_bytes_accessed`` from
      ``compiled.cost_analysis()``): ``step_ms * collective_bytes /
      bytes_accessed``. The wall clock is real; the ATTRIBUTION assumes
      collective bytes cost what average program bytes cost — truthful
      about magnitude on memory-bound steps, silent about overlap.
      Recorded only when the caller has cost analysis (xla_stats).
    """
    reg = registry()
    out = {}
    if fwd_s is not None:
        out["fwd_ms"] = fwd_s * 1e3
    if fwdbwd_s is not None and fwd_s is not None:
        out["bwd_ms"] = max(fwdbwd_s - fwd_s, 0.0) * 1e3
    if step_s is not None:
        out["step_ms"] = step_s * 1e3
        if fwdbwd_s is not None:
            out["optim_ms"] = max(step_s - fwdbwd_s, 0.0) * 1e3
    if comm_bytes is not None:
        out["comm_ms"] = estimate_comm_ms(comm_bytes, platform)
        if step_s is not None and cost_bytes_accessed:
            share = min(float(comm_bytes) / float(cost_bytes_accessed),
                        1.0)
            out["comm_measured_ms"] = step_s * 1e3 * share
    for k, v in out.items():
        reg.gauge(f"phase/{k[:-3]}_ms").set(round(v, 4))
    return {k: round(v, 4) for k, v in out.items()}


def tokens_in_batch(batch) -> int:
    """Throughput accounting for a step's batch: ``batch*seq`` when the
    first array-like argument is a 2-d INTEGER array (a token grid),
    else its ``batch`` dim (sample count — a [N,C,H,W] image batch must
    not scale with channels). Labels/aux inputs ride dim-0-aligned with
    the first, so the first is the truthful count."""
    for b in batch:
        shape = getattr(b, "shape", None)
        if shape is None or len(shape) == 0:
            continue
        if len(shape) == 2 and "int" in str(getattr(b, "dtype", "")):
            return int(shape[0]) * int(shape[1])
        return int(shape[0])
    return 0
