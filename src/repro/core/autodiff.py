"""Reverse-mode autodiff over a captured region graph.

The training tentpole: instead of handing the whole step to ``jax.grad``
as one opaque callable, the backward is derived *as a TaskGraph* — one
VJP node (or a native transpose node) per forward node — so the joint
fwd+bwd graph flows through the same CSE / fusion / epilogue passes and
the scheduler fuses ACROSS the fwd/bwd boundary.

Bitwise contract with the per-op reference (``train/step.py``):

* The backward is derived over the graph AFTER ``passes.optimize_graph``
  (expose + CSE + fusion, no prune).  The per-op path runs those same
  per-call fusions before ``jax.grad`` ever sees the computation — e.g.
  the QKV wide GEMM — so differentiating the *fused* forms is what makes
  ``d_x`` accumulate in the same shapes, with the same dot-generals, as
  the reference backward.
* The generic rule IS ``jax.vjp`` of the node's own lowering
  (``lowering.node_callable``, impl/tile resolved at derivation time by
  the exact roofline argmin the final pipeline re-binds).  Per-node VJP
  composed along the graph is the same chain of per-primitive transposes
  ``jax.grad`` runs over the composite.
* Cotangent fan-in accumulates pairwise in reverse topological order,
  mirroring ``jax``'s ``backward_pass`` write-then-add discipline.

Recompute-vs-store (remat) is a *schedule* decision here, not a numeric
one: both choices replay the identical ops.  ``"store"`` leaves the VJP's
internal forward replay CSE-able against the forward instance (XLA shares
the residual); ``"recompute"`` pins an ``optimization_barrier`` on the
VJP's differentiated primals so the replay cannot be shared and the
residual is recomputed in the backward.  The choice comes from the remat
arm of ``core.schedule.CostModel`` (``pick_remat``), recorded on
``Node.schedule.remat`` (part of the graph signature) and surfaced by
``tapir.explain()``.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .ir import LIBRARY_OPS, Node, TaskGraph, TensorType, _freeze
from .lowering import node_callable
from .passes import mesh_fingerprint, optimize_graph
from .schedule import (CostModel, matmul_tiles, note_weight_passes,
                       pick_attention_tiles, pick_gqa_impl, pick_impl,
                       pick_remat, pick_scan_chunk)

__all__ = ["grad"]


def _is_float(ttype: TensorType) -> bool:
    return jnp.issubdtype(jnp.dtype(ttype.dtype), jnp.inexact)


def _operands(node: Node) -> tuple[int, ...]:
    """Data operands in lowering order: ``inputs`` then epilogue extras."""
    return tuple(node.inputs) + tuple(
        e for _, extras, _ in node.epilogue for e in extras)


# ---------------------------------------------------------------------------
# Generic rule: jax.vjp of the node's own lowering
# ---------------------------------------------------------------------------

#: Structural-key -> vjp callable.  Identity-stable memoization matters
#: twice over: the fn object is part of the pyfunc node's signature (same
#: captured step must replay the same region program), and a plain closure
#: (not a callable instance) digests cross-process by code identity for
#: the L2 program cache.
_VJP_FNS: dict[tuple, Callable] = {}


def _make_vjp_fn(call: Callable, diff: tuple[int, ...], remat: str) -> Callable:
    def _node_vjp(ct, *vals, **_static):
        prim = [vals[i] for i in diff]
        if remat == "recompute":
            # the barrier makes the replayed forward un-CSE-able against
            # the forward instance: the residual is recomputed here, in
            # the backward, instead of being stored across the boundary.
            # Same ops, same bits — only HBM residency changes.
            prim = list(jax.lax.optimization_barrier(tuple(prim)))

        def _restricted(*dp):
            full = list(vals)
            for j, i in enumerate(diff):
                full[i] = dp[j]
            return call(*full)

        _, vjp = jax.vjp(_restricted, *prim)
        return vjp(ct)

    return _node_vjp


def _vjp_fn_for(g: TaskGraph, node: Node, diff: tuple[int, ...], remat: str,
                backend: str, bf16_partials: bool) -> Callable:
    frozen_attrs = tuple(sorted((k, _freeze(v)) for k, v in node.attrs.items()))
    key = (node.op, node.ttype, frozen_attrs, node.pdims, node.rdims,
           tuple((fn, len(extras), _freeze(at))
                 for fn, extras, at in node.epilogue),
           node.schedule.impl, tuple(sorted(node.schedule.tile.items())),
           tuple(g.nodes[o].ttype for o in _operands(node)),
           diff, remat, backend, bf16_partials)
    fn = _VJP_FNS.get(key)
    if fn is None:
        fn = _make_vjp_fn(node_callable(node, backend, bf16_partials),
                          diff, remat)
        _VJP_FNS[key] = fn
    return fn


def _resolve_library_schedule(g: TaskGraph, node: Node, cm: CostModel,
                              backend: str, mesh_axes: dict,
                              forced: dict) -> None:
    """Bind tile + impl on a library node at derivation time, with the
    exact same argmin ``assign_schedules`` re-binds on the joint graph —
    the VJP must replay the forward through the impl that actually runs."""
    if node.op == "matmul":
        node.schedule.tile = matmul_tiles(g, node, cm)
    elif node.op == "attention":
        _, s, _, d_ = node.attrs["q_shape"]
        node.schedule.tile = pick_attention_tiles(
            s, node.attrs["kv_len"], d_, node.ttype.dtype, cm)
        node.attrs["gqa_impl"] = pick_gqa_impl(node, cm, backend,
                                               mesh_axes=mesh_axes)
    elif node.op == "linear_scan":
        q_t = g.nodes[node.inputs[0]].ttype
        d_v = g.nodes[node.inputs[2]].ttype.shape[-1]
        node.schedule.tile = {"chunk": pick_scan_chunk(
            node.attrs["seq"], q_t.shape[-1], d_v, node.ttype.dtype, cm)}
    if node.attrs.get("exposed", False):
        pick_impl(g, node, cm, backend, mesh_axes=mesh_axes,
                  forced=forced.get(node.op))
        if node.op == "matmul":
            note_weight_passes(g, node)
    elif node.op in LIBRARY_OPS and not node.schedule.impl:
        node.schedule.impl = "opaque"


# ---------------------------------------------------------------------------
# Native transpose rules — structural ops whose VJP is another structural
# node (keeps the bwd graph pass-transparent; all bitwise-equal to the
# jax transpose of the same primitive)
# ---------------------------------------------------------------------------

def _rule_reshape(g, node, ct, in_t):
    return g.add("reshape", (ct,), TensorType(in_t.shape, in_t.dtype),
                 pdims=tuple(range(len(in_t.shape))))


def _rule_transpose(g, node, ct, in_t):
    perm = node.attrs["perm"]
    inv = tuple(sorted(range(len(perm)), key=lambda i: perm[i]))
    return g.add("transpose", (ct,), TensorType(in_t.shape, in_t.dtype),
                 pdims=tuple(range(len(in_t.shape))), perm=inv)


def _rule_convert(g, node, ct, in_t):
    return g.add("convert", (ct,), TensorType(in_t.shape, in_t.dtype),
                 pdims=tuple(range(len(in_t.shape))))


# ---------------------------------------------------------------------------
# The derivation
# ---------------------------------------------------------------------------

def grad(loss, wrt, policy: str = "auto", keep=()):
    """Derive the backward of ``loss`` w.r.t. ``wrt`` inside the open region.

    ``loss``/``wrt`` are region handles (``TracedTensor``): the scalar
    loss and the parameter leaves.  Must be called while the only other
    live handles are region *inputs* or listed in ``keep`` — the forward
    is optimized in place (CSE/fusion may retire interior nodes) before
    the backward is grown.  ``keep`` handles (e.g. an earlier
    microbatch's loss/grad nodes) are threaded through the optimization
    as extra graph outputs so they survive CSE/DCE.

    Returns ``(loss_handle, grad_handles)`` — fresh handles valid after
    the in-place optimization — and attaches a ``grad_meta`` stats dict
    to the graph for ``tapir.explain()``.  With a non-empty ``keep``,
    returns ``(loss_handle, grad_handles, keep_handles)`` where
    ``keep_handles`` rebind the kept values post-optimization.
    """
    reg = loss._region
    g: TaskGraph = reg.g
    cfg = reg.cfg
    cm = cfg.resolved_cost_model()
    backend = cfg.resolved_backend()
    mesh_axes = dict(mesh_fingerprint())
    forced = dict(cfg.force_impl or ())

    wrt_nids = [reg.nid_of(h) for h in wrt]
    # ttypes up front: an input leaf the loss never touches is dropped by
    # the optimization passes below, but still owes a zeros cotangent
    wrt_ts = [g.nodes[w].ttype for w in wrt_nids]
    keep = tuple(keep)
    g.set_outputs([reg.nid_of(loss)] + [reg.nid_of(h) for h in keep])
    if cfg.mode == "tapir":
        optimize_graph(g, cm)      # differentiate the FUSED forms
    loss_nid = g.outputs[0]
    keep_nids = list(g.outputs[1:])

    order = g.topo_order()         # forward, reachable-from-loss only
    n_fwd = len(order)

    # needs-grad: float nodes forward-reachable from any wrt input
    need: set[int] = set(wrt_nids)
    for nid in order:
        node = g.nodes[nid]
        if nid in need or not _is_float(node.ttype):
            continue
        if any(o in need for o in _operands(node)):
            need.add(nid)

    meta = {"n_fwd": n_fwd, "n_bwd": 0, "remat": {"store": 0, "recompute": 0},
            "bytes_stored": 0, "bytes_recomputed": 0}
    loss_t = g.nodes[loss_nid].ttype
    ct: dict[int, int] = {
        loss_nid: g.add("const", (), TensorType((), loss_t.dtype), value=1.0)}

    def _accumulate(operand: int, contrib: int) -> None:
        prev = ct.get(operand)
        if prev is None:
            ct[operand] = contrib
        else:
            t = g.nodes[operand].ttype
            ct[operand] = g.add("ew", (prev, contrib), t, fn="add",
                                pdims=tuple(range(len(t.shape))))

    for nid in reversed(order):
        node = g.nodes[nid]
        c = ct.get(nid)
        if c is None or node.op in ("input", "const"):
            continue
        operands = _operands(node)
        # native structural transposes (single-operand, shape-preserving-ish)
        if node.op in ("reshape", "transpose", "convert") and not node.epilogue:
            src = operands[0]
            if src in need:
                rule = {"reshape": _rule_reshape, "transpose": _rule_transpose,
                        "convert": _rule_convert}[node.op]
                _accumulate(src, rule(g, node, c, g.nodes[src].ttype))
                meta["n_bwd"] += 1
            continue
        if node.op == "ew" and not node.epilogue and node.attrs["fn"] in (
                "add", "sub", "neg") and all(
                g.nodes[o].ttype.shape == node.ttype.shape for o in operands):
            fn = node.attrs["fn"]
            if fn in ("add", "sub") and operands[0] in need:
                _accumulate(operands[0], c)
                meta["n_bwd"] += 1
            if fn in ("sub", "neg"):
                tgt = operands[0] if fn == "neg" else operands[1]
                if tgt in need:
                    t = g.nodes[tgt].ttype
                    neg = g.add("ew", (c,), t, fn="neg",
                                pdims=tuple(range(len(t.shape))))
                    _accumulate(tgt, neg)
                    meta["n_bwd"] += 1
            elif fn == "add" and operands[1] in need:
                _accumulate(operands[1], c)
                meta["n_bwd"] += 1
            continue
        # generic rule: jax.vjp of this node's own lowering
        diff = tuple(i for i, o in enumerate(operands)
                     if o in need and _is_float(g.nodes[o].ttype))
        if not diff:
            continue
        if node.op in LIBRARY_OPS or node.op in ("matmul", "attention",
                                                 "linear_scan", "conv2d"):
            _resolve_library_schedule(g, node, cm, backend, mesh_axes, forced)
        remat = node.schedule.remat
        if not remat:
            remat = pick_remat(g, node, cm, policy=policy)
            node.schedule.remat = remat
            meta["remat"][remat] += 1
            meta["bytes_stored" if remat == "store"
                 else "bytes_recomputed"] += int(node.ttype.bytesize)
        fn = _vjp_fn_for(g, node, diff, remat, backend, cfg.bf16_partials)
        for j, i in enumerate(diff):
            o = operands[i]
            o_t = g.nodes[o].ttype
            contrib = g.add(
                "pyfunc", (c,) + operands, o_t,
                pdims=tuple(range(len(o_t.shape))),
                sharding=g.nodes[o].sharding,
                fn=fn, out=j,
                static=(("grad_of", node.op), ("remat", remat)))
            _accumulate(o, contrib)
            meta["n_bwd"] += 1

    grads = []
    for w, t in zip(wrt_nids, wrt_ts):
        cn = ct.get(w)
        if cn is None:            # unused param: jax.grad returns zeros
            z = g.add("const", (), TensorType((), t.dtype), value=0.0)
            cn = g.add("broadcast", (z,), t,
                       pdims=tuple(range(len(t.shape))))
        grads.append(reg.handle(cn))

    g.grad_meta = meta
    if keep:
        return (reg.handle(loss_nid), grads,
                [reg.handle(n) for n in keep_nids])
    return reg.handle(loss_nid), grads
