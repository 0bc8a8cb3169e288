"""Trace-time distributed context.

The strategy compiler / hybrid trainers set this scope while tracing the
model so that layers (e.g. GPTAttention) can dispatch to mesh-aware
implementations (ring attention over 'sp') without threading the mesh
through every ``forward`` signature. The reference threads the analogous
information through per-rank rewritten programs + global collective ring
ids (reference: fleet meta-optimizers inserting c_* ops keyed by ring_id,
meta_optimizers/common.py); here it is a trace-scoped (mesh, axis) pair.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh

_SP: Optional[Tuple[Mesh, str, bool]] = None


@contextlib.contextmanager
def sequence_parallel_scope(mesh: Mesh, axis_name: str = "sp"):
    """Within this scope, attention layers use ring attention over
    ``axis_name`` (when the axis is larger than 1)."""
    global _SP
    prev = _SP
    _SP = (mesh, axis_name, False) if mesh.shape.get(axis_name, 1) > 1 \
        else None
    try:
        yield
    finally:
        _SP = prev


@contextlib.contextmanager
def manual_sequence_parallel_scope():
    """Marks that the surrounding code is ALREADY manual over the sp axis
    (e.g. inside the pipeline's shard_map, distributed/pipeline.py) — the
    attention layer then calls the inside-shard_map ring directly instead
    of opening a nested shard_map over the same axis."""
    global _SP
    prev = _SP
    if prev is not None:
        _SP = (prev[0], prev[1], True)
    try:
        yield
    finally:
        _SP = prev


def current_sequence_parallel() -> Optional[Tuple[Mesh, str, bool]]:
    return _SP


_AUTO_AXES: Optional[Tuple[Mesh, Tuple[str, ...]]] = None


@contextlib.contextmanager
def auto_axes_scope(mesh: Mesh, axes):
    """Declares the axes of ``mesh`` that are still GSPMD-auto where the
    model is being traced. XLA does not partition a Mosaic (Pallas)
    call: in a jit region with any auto axis of more than one device —
    fully auto as much as partially manual — lowering for a TPU fails
    with "Mosaic kernels cannot be automatically partitioned. Please
    wrap the call in a shard_map." So kernels consult this scope and
    open a nested shard_map over the listed axes (flash_attention.py,
    ring_attention.py). Trainers open it through ``kernel_scope``.
    A CPU mesh never shows the refusal (interpret mode is plain HLO);
    tests/test_tpu_lowering.py compiles for a v5e topology to see it."""
    global _AUTO_AXES
    prev = _AUTO_AXES
    _AUTO_AXES = (mesh, tuple(axes))
    try:
        yield
    finally:
        _AUTO_AXES = prev


def current_auto_axes() -> Optional[Tuple[Mesh, Tuple[str, ...]]]:
    return _AUTO_AXES


@contextlib.contextmanager
def kernel_scope(mesh: Mesh):
    """``auto_axes_scope`` over whatever axes of ``mesh`` are auto at
    this point of the trace, read from jax's own context: every axis in
    a plain jit, the axes a surrounding shard_map left auto inside one,
    none (no scope) inside an all-manual region or on a one-device
    mesh."""
    am = jax.sharding.get_abstract_mesh()
    manual = () if am.empty else am.manual_axes
    axes = tuple(a for a in mesh.axis_names if a not in manual)
    if mesh.size == 1 or not axes:
        yield
        return
    with auto_axes_scope(mesh, axes):
        yield


def kernel_auto_axes() -> Optional[Tuple[Mesh, Tuple[str, ...]]]:
    """(mesh, axes) a Mosaic kernel traced here has to make manual, or
    None when it can be called as it is: no scope is open, or the
    target is the CPU, where the kernel is interpreted into plain HLO.
    One copy, consulted by flash_attention and ring_attention."""
    from ..core.place import target_platform

    if _AUTO_AXES is None or target_platform() == "cpu":
        return None
    return _AUTO_AXES


def nested_kernel_shard(fn, in_specs, out_specs):
    """Single shared implementation of the "make every axis manual
    around a Mosaic kernel" rule: wraps ``fn`` in a shard_map over the
    open scope's auto axes. Inside another shard_map (the pipeline's,
    manual over 'pp') the context mesh is an AbstractMesh with those
    axes already Manual, and the nested shard_map must be given that
    mesh; in a plain jit there is none and the scope's concrete mesh is
    used."""
    mesh, axes = _AUTO_AXES
    am = jax.sharding.get_abstract_mesh()
    return jax.shard_map(fn, mesh=mesh if am.empty else am,
                         in_specs=in_specs, out_specs=out_specs,
                         axis_names=frozenset(axes), check_vma=False)
