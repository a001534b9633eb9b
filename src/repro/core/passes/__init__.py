"""Optimization pipeline over the Task IR.

Mirrors TapirXLA's split:

* ``mode="tapir"``   — expose library internals (inline), optimize the
  parallel graph (cse, fusion), then schedule *late* (strip-mining +
  small-task serialization in ``core.schedule``).
* ``mode="opaque"``  — stock-XLA control: early per-op heuristics, library
  calls sealed, no cross-op fusion.
"""
from __future__ import annotations

import dataclasses

from repro.dist.sharding import current_mesh

from ..ir import TaskGraph
from ..schedule import CostModel, assign_early_heuristics, assign_schedules
from .cse import cse
from .fusion import fuse_added_gemms, fuse_epilogues, fuse_shared_input
from .inline import expose_libraries, seal_libraries


def ambient_mesh():
    """The ambient mesh, or None.  Runs on the op-dispatch hot path (part
    of every cache key)."""
    return current_mesh()


def mesh_has_model_axis() -> bool:
    """True when an ambient mesh with a "model" axis is active — sharded
    execution, where fusion shape must keep TP shards slice-aligned."""
    m = ambient_mesh()
    return m is not None and "model" in m.axis_names


#: last (mesh object, fingerprint) — a mesh's axes/sizes are immutable,
#: and the fingerprint sits on the op-dispatch hot path (every cache
#: key), so the tuple build runs once per mesh, not once per op
_fp_cache: tuple = (None, ())


def mesh_fingerprint() -> tuple:
    """Full structural identity of the ambient mesh: ((axis, size), ...)
    pairs, or () with no mesh.  Part of every compile-cache key — two
    different meshes must never replay each other's programs (a program
    compiled for model=4 is WRONG under model=2 even though both "have a
    model axis"), and the sharding constraints captured on region nodes
    are resolved against a specific mesh shape."""
    global _fp_cache
    m = ambient_mesh()
    if m is None:
        return ()
    cached_m, fp = _fp_cache
    if cached_m is m:
        return fp
    shape = m.shape
    fp = tuple((a, int(shape[a])) for a in m.axis_names)
    _fp_cache = (m, fp)
    return fp


def optimize_graph(g: TaskGraph, cm: CostModel) -> TaskGraph:
    """The optimization half of the tapir pipeline (expose + CSE + fusion),
    without pruning or scheduling.  ``core.autodiff`` runs this over a
    training capture BEFORE deriving the backward, so the VJP rules
    differentiate exactly the fused forms the per-op path executes (the
    same per-call fusions, e.g. the QKV wide GEMM) — and ``run_pipeline``
    re-runs it over the joint fwd+bwd graph, where it is idempotent on the
    already-fused forward and additionally fuses across the fwd/bwd
    boundary."""
    expose_libraries(g)
    cse(g)
    fuse_added_gemms(g)
    cse(g)
    # fusion SHAPE is a late-scheduling decision: one wide GEMM for BLAS
    # targets, stacked batched GEMM on the TPU target AND whenever a model
    # axis is active — the concat form puts segment boundaries inside TP
    # shards, which GSPMD lowers to halo permutes and (on this jaxlib's CPU
    # SPMD partitioner) miscompiles outright when one misaligned slice
    # carries a model-axis constraint while its siblings don't
    fuse_shared_input(g, stacked=cm.name.startswith("tpu")
                      or mesh_has_model_axis())
    fuse_epilogues(g)
    return g


def run_pipeline(g: TaskGraph, mode: str, cm: CostModel, backend: str,
                 ablate_serialization: bool = False,
                 force_impl: tuple | None = None) -> TaskGraph:
    if mode == "opaque":
        seal_libraries(g)
        assign_early_heuristics(g, cm)
        g.prune()
        return g
    assert mode == "tapir", mode
    optimize_graph(g, cm)
    g.prune()
    # replace() keeps every other constant (grain_bytes, spawn_s, score
    # passes, ...) — a field-by-field rebuild silently reset the ones it
    # forgot to copy
    cm_eff = cm if not ablate_serialization else dataclasses.replace(
        cm, name=cm.name + "+noserial", grain_flops=0.0)
    # per-shard costs: nodes carrying a sharding constraint do 1/shard of
    # the work per device — grain/impl decisions must see per-shard numbers
    assign_schedules(g, cm_eff, backend=backend,
                     mesh_axes=dict(mesh_fingerprint()),
                     force_impl=force_impl)
    return g
