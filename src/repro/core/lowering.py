"""Lowering: scheduled Task IR -> JAX computation.

The emitter walks the graph in topological order and produces a python
callable (traced under ``jax.jit`` by callers).  The *same* graph lowers
differently depending on the schedule the passes attached: each library
node dispatches on ``node.schedule.impl`` — the name the scheduler's impl
registry (``core.schedule.IMPL_REGISTRY``) bound as the roofline argmin
over that op's candidate lowerings.  No backend flag or shape threshold is
re-derived here; the cost model already decided.

* kernel impls (``flash_kernel`` / ``fused_kernel`` / ``paged_kernel`` /
  ``kernel``) lower to Pallas kernels (TPU target; interpret mode in
  tests) with fused epilogues executed inside the kernel;
* jnp impls (``blockwise`` / ``chunked`` / ``materialized_*`` / ``einsum``
  / ``ref`` / ``gathered`` / ``composite``) lower to fused jnp composites —
  ``blockwise``/``chunked`` keep their loop bodies under the
  ``tapir_vmem_body`` scope so ``launch.hlo_cost`` can discount
  VMEM-resident traffic;
* ``"opaque"`` (sealed ops, early-heuristic mode) lowers the way stock XLA
  emitted Eigen calls: isolated per-op calls, per-expert loops for batched
  GEMMs, materialized attention scores, sequential scans.

An empty ``impl`` (a graph emitted without scheduling) falls back by the
``exposed`` attr alone.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .ir import Node, TaskGraph

# -- pyfunc jit units ---------------------------------------------------------

#: (fn, static) -> jitted callable.  A pyfunc node lowers through a jit
#: BOUNDARY, not an inline call: jax transposes a pjit as a unit, finishing
#: the fn's internal cotangent accumulation before the caller adds sibling
#: contributions — the same association the eager path's module-level
#: ``jax.jit(fn)`` wrappers produce.  Inlining the fn instead would let a
#: whole-region ``jax.grad`` interleave those adds and drift in the last
#: ulp from both the eager path and the per-node VJP of ``core.autodiff``.
#: (XLA inlines the call again, so forward bits are unchanged.)
_PYFUNC_JITS: dict = {}


def _pyfunc_jit(fn: Callable, static) -> Callable:
    key = (fn, tuple(static))
    jfn = _PYFUNC_JITS.get(key)
    if jfn is None:
        jfn = jax.jit(partial(fn, **dict(static)))
        _PYFUNC_JITS[key] = jfn
    return jfn


# -- elementwise registry ----------------------------------------------------

_EW: dict[str, Callable] = {
    "add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply,
    "div": jnp.divide, "maximum": jnp.maximum, "minimum": jnp.minimum,
    "neg": jnp.negative, "exp": jnp.exp, "log": jnp.log,
    "rsqrt": jax.lax.rsqrt, "square": jnp.square, "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid, "relu": jax.nn.relu, "gelu": jax.nn.gelu,
    "silu": jax.nn.silu, "abs": jnp.abs, "sqrt": jnp.sqrt,
}


def _apply_epilogue(y, node: Node, env: dict) -> Any:
    for fn, extras, at in node.epilogue:
        # Replay the un-fused chain bitwise: the head materialized in the
        # consumer's dtype before the ew op ran, so a bf16 residual add
        # happens in bf16 — not on the f32 accumulator.  Fusion must not
        # change WHAT is computed, only when the output round-trips HBM.
        edt = at.get("dtype")
        if edt is not None:
            y = y.astype(edt)
        vals = [env[e] for e in extras]
        vals = [v.astype(y.dtype) if hasattr(v, "astype") else v for v in vals]
        f = _EW[fn]
        if at.get("head_pos", 0) == 0:
            y = f(y, *vals)
        else:  # head is the second operand of a binary fn
            y = f(vals[0], y, *vals[1:])
    return y


# -- library lowerings --------------------------------------------------------


def _lower_matmul(node: Node, env: dict, backend: str,
                  bf16_partials: bool = False) -> Any:
    x, w = env[node.inputs[0]], env[node.inputs[1]]
    out_dtype = node.ttype.dtype
    exposed = node.attrs.get("exposed", False)
    # bf16_partials: let k-sharded partial sums leave the dot in bf16 so
    # the TP all-reduce carries half the bytes (MXU still accumulates f32
    # inside the dot for bf16 operands)
    if bf16_partials and x.dtype == jnp.bfloat16 and exposed:
        acc = jnp.bfloat16
    else:
        acc = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype

    impl = node.schedule.impl or ("einsum" if exposed else "opaque")
    if impl == "fused_kernel" and w.ndim == 2:
        from repro.kernels import fused_matmul as fm
        # the custom-VJP form, so a captured training step can
        # differentiate through the kernel (core.autodiff runs jax.vjp
        # over this lowering)
        vals = tuple(env[e] for _, extras, _ in node.epilogue
                     for e in extras)
        stages = tuple((fn, len(extras), tuple(sorted(at.items())))
                       for fn, extras, at in node.epilogue)
        return fm.ops.fused_matmul_vjp(
            x, w, vals, stages, out_dtype,
            tuple(sorted(node.schedule.tile.items())), backend != "tpu")

    if w.ndim == 3 and node.attrs.get("stacked", False):
        # shared-input (QKV) fusion: one batched GEMM over stacked weights;
        # each stack slot keeps its own TP shard (no misaligned slices)
        y = jnp.einsum("...k,nkw->n...w", x, w, preferred_element_type=acc)
    elif w.ndim == 3 and impl == "opaque":
        # opaque mode: per-expert "library calls" — an isolated GEMM per
        # leading-dim slice, exactly how pre-fusion XLA emitted MoE experts.
        outs = [jnp.matmul(x[e], w[e], preferred_element_type=acc)
                for e in range(w.shape[0])]
        y = jnp.stack(outs, axis=0)
    elif w.ndim == 3:
        y = jnp.einsum("e...mk,ekn->e...mn", x, w, preferred_element_type=acc)
    else:
        y = jnp.matmul(x, w, preferred_element_type=acc)
    y = _apply_epilogue(y, node, env)
    return y.astype(out_dtype)


def _lower_attention(node: Node, env: dict, backend: str) -> Any:
    q, k, v = (env[i] for i in node.inputs[:3])
    bias = env[node.inputs[3]] if len(node.inputs) > 3 else None
    causal = node.attrs.get("causal", False)
    exposed = node.attrs.get("exposed", False)
    out_dtype = node.ttype.dtype

    impl = node.schedule.impl or ("ref" if exposed else "opaque")

    if impl == "opaque":
        # sealed: materialized score matrix, separate softmax ops, repeated
        # KV, and no fused epilogue — exactly how stock XLA emitted it
        y = _materialized_attention(q, k, v, causal, bias, grouped=False)
        return y.astype(out_dtype)

    if impl == "flash_kernel":
        from repro.kernels import flash_attention as fa
        # custom-VJP wrapper: the kernel forward stays a Pallas call and
        # the backward is the recompute-based flash gradient
        y = fa.ops.flash_attention_vjp(
            q, k, v, causal, node.schedule.tile.get("bq", 128),
            node.schedule.tile.get("bkv", 128), backend != "tpu")
    elif impl == "blockwise":
        from repro.kernels import flash_attention as fa
        # online-softmax over KV blocks (never materializes scores).  The
        # named scope marks the loop body as VMEM-resident on the TPU
        # target (the Pallas kernel keeps score/accumulator tiles
        # on-chip); launch.hlo_cost discounts these ops' HBM traffic.
        with jax.named_scope("tapir_vmem_body"):
            y = fa.ops.flash_attention_jnp(
                q, k, v, causal=causal,
                block_kv=node.schedule.tile.get("bkv", 1024))
    elif impl in ("materialized_repeat", "materialized_grouped"):
        y = _materialized_attention(q, k, v, causal, bias,
                                    grouped=impl == "materialized_grouped")
    else:  # "ref": fused composite — one expression, fp32 accum, grouped KV
        from repro.kernels import flash_attention as fa
        y = fa.ref.attention_ref(q, k, v, causal=causal, bias=bias)
    return _apply_epilogue(y, node, env).astype(out_dtype)


def _lower_paged_attention(node: Node, env: dict, backend: str) -> Any:
    from repro.kernels import paged_attention as pa
    q, ck, cv, ptab, lengths = (env[i] for i in node.inputs)
    if node.schedule.impl == "paged_kernel":
        return pa.ops.paged_attention(
            q, ck, cv, ptab, lengths,
            pages_per_block=node.schedule.tile["pages_per_block"],
            interpret=backend != "tpu")
    # "gathered", and the sealed/unscheduled forms of the same composite
    return pa.ref.paged_attention_gathered(q, ck, cv, ptab, lengths)


def _lower_ssm_state_step(node: Node, env: dict, backend: str) -> Any:
    """Both output nodes lower through ONE call, memoized in ``env``
    under the operands' nids: the kernel (or composite) runs once."""
    key = ("ssm_state_step",) + tuple(node.inputs)
    res = env.get(key)
    if res is None:
        from repro.kernels import ssm_state_step as ss
        args = [env[i] for i in node.inputs]
        if node.schedule.impl == "kernel":
            res = ss.ops.ssm_state_step(*args, interpret=backend != "tpu")
        else:
            # "composite", and the sealed/unscheduled forms of the same
            res = ss.ref.ssm_state_step_ref(*args)
        env[key] = res
    return res[node.attrs["out"]]


def _materialized_attention(q, k, v, causal, bias, grouped=False):
    hq, hkv = q.shape[2], k.shape[2]
    scale = 1.0 / np.sqrt(q.shape[-1])
    grp = hq // hkv
    if grouped and grp > 1:
        # exposed path: reshape q into [B,S,Hkv,grp,D] so each kv head is
        # contracted against its whole query group in one einsum; head index
        # hkv*grp + g matches the repeat layout exactly.
        B, sq, _, d = q.shape
        skv = k.shape[1]
        qg = q.reshape(B, sq, hkv, grp, d)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                       preferred_element_type=jnp.float32) * scale
        s = s.reshape(B, hq, sq, skv)
    else:
        if hkv != hq:
            k = jnp.repeat(k, grp, axis=2)
            v = jnp.repeat(v, grp, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    if causal:
        sq, skv = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    if grouped and grp > 1:
        B, _, sq, skv = p.shape
        pg = p.reshape(B, hkv, grp, sq, skv)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", pg.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, sq, hq, v.shape[-1])
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _lower_linear_scan(node: Node, env: dict, backend: str) -> Any:
    from repro.kernels import linear_scan as ls
    q, k, v, w = (env[i] for i in node.inputs[:4])
    u = env[node.inputs[4]] if len(node.inputs) > 4 else None
    exposed = node.attrs.get("exposed", False)
    out_dtype = node.ttype.dtype
    impl = node.schedule.impl or ("chunked" if exposed else "opaque")
    if impl == "kernel":
        # custom-VJP form: differentiable inside a captured training step
        y = ls.ops.linear_scan_vjp(q, k, v, w, u,
                                   node.schedule.tile.get("chunk", 128),
                                   backend != "tpu")
    elif impl == "chunked":
        # chunk-body intermediates are VMEM-resident in the Pallas kernel
        # on the TPU target (see launch.hlo_cost)
        with jax.named_scope("tapir_vmem_body"):
            y = ls.ops.linear_scan_chunked(
                q, k, v, w, u=u,
                chunk=node.schedule.tile.get("chunk", 128))
    else:  # "ref" / "opaque": the sequential element recurrence
        y = ls.ref.linear_scan_ref(q, k, v, w, u=u)
    return _apply_epilogue(y, node, env).astype(out_dtype)


def _lower_conv2d(node: Node, env: dict, backend: str) -> Any:
    x, k = env[node.inputs[0]], env[node.inputs[1]]
    out_dtype = node.ttype.dtype
    y = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), k.astype(jnp.float32),
        window_strides=node.attrs["strides"],
        padding=node.attrs["padding"],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y = _apply_epilogue(y, node, env)
    return y.astype(out_dtype)


# -- primitive lowerings -------------------------------------------------------


def _resolve_starts(node: Node, env: dict, dyn_inputs: tuple) -> tuple:
    """Interleave static int starts with dynamic scalar operands (the None
    holes of ``static_starts`` consume ``dyn_inputs`` in order)."""
    it = iter(dyn_inputs)
    return tuple(s if s is not None else env[next(it)]
                 for s in node.attrs["static_starts"])


def _lower_node(node: Node, env: dict, inputs: dict, backend: str,
                bf16_partials: bool = False) -> Any:
    op = node.op
    if op == "input":
        return inputs[node.attrs["name"]]
    if op == "const":
        return jnp.asarray(node.attrs["value"], dtype=node.ttype.dtype)
    if op == "ew":
        vals = [env[i] for i in node.inputs]
        return _EW[node.attrs["fn"]](*vals)
    if op == "reduce":
        x = env[node.inputs[0]]
        fn = {"sum": jnp.sum, "max": jnp.max, "mean": jnp.mean}[node.attrs["fn"]]
        return fn(x, axis=node.attrs["axes"], keepdims=node.attrs.get("keepdims", False))
    if op == "softmax":
        return jax.nn.softmax(env[node.inputs[0]], axis=node.attrs.get("axis", -1))
    if op == "reshape":
        return jnp.reshape(env[node.inputs[0]], node.ttype.shape)
    if op == "transpose":
        return jnp.transpose(env[node.inputs[0]], node.attrs["perm"])
    if op == "broadcast":
        return jnp.broadcast_to(env[node.inputs[0]], node.ttype.shape)
    if op == "slice":
        x = env[node.inputs[0]]
        ax = node.attrs["axis"] % x.ndim
        idx = [slice(None)] * x.ndim
        idx[ax] = slice(node.attrs["start"], node.attrs["limit"])
        return x[tuple(idx)]
    if op == "concat":
        return jnp.concatenate([env[i] for i in node.inputs],
                               axis=node.attrs["axis"])
    if op == "select":
        p, a, b = (env[i] for i in node.inputs)
        return jnp.where(p, a, b)
    if op == "convert":
        return env[node.inputs[0]].astype(node.ttype.dtype)
    if op == "iota":
        return jax.lax.iota(node.ttype.dtype, node.ttype.shape[0])
    if op == "pyfunc":
        vals = [env[i] for i in node.inputs]
        res = _pyfunc_jit(node.attrs["fn"],
                          node.attrs.get("static", ()))(*vals)
        out_i = node.attrs.get("out")
        return res if out_i is None else res[out_i]
    if op == "index":
        from .tapir import decode_index
        return env[node.inputs[0]][decode_index(node.attrs["idx"])]
    if op == "dynamic_slice":
        buf = env[node.inputs[0]]
        starts = _resolve_starts(node, env, node.inputs[1:])
        return jax.lax.dynamic_slice(buf, starts, node.attrs["sizes"])
    if op == "dynamic_update_slice":
        buf, upd = env[node.inputs[0]], env[node.inputs[1]]
        starts = _resolve_starts(node, env, node.inputs[2:])
        upd = jnp.asarray(upd).astype(buf.dtype).reshape(node.attrs["window"])
        return jax.lax.dynamic_update_slice(buf, upd, starts)
    if op == "gather":
        src = env[node.inputs[0]]
        idx = tuple(env[i] for i in node.inputs[1:])
        return src[idx]
    if op == "scatter":
        n_idx = node.attrs["n_idx"]
        if node.attrs.get("zero_init", False):
            buf = jnp.zeros(node.ttype.shape, node.ttype.dtype)
            rest = node.inputs
        else:
            buf = env[node.inputs[0]]
            rest = node.inputs[1:]
        idx = tuple(env[i] for i in rest[:n_idx])
        upd = jnp.asarray(env[rest[n_idx]]).astype(buf.dtype)
        at = buf.at[idx]
        if node.attrs.get("mode", "set") == "add":
            return at.add(upd, mode="drop")
        return at.set(upd, mode="drop")
    if op == "matmul":
        return _lower_matmul(node, env, backend, bf16_partials)
    if op == "attention":
        return _lower_attention(node, env, backend)
    if op == "paged_attention":
        return _lower_paged_attention(node, env, backend)
    if op == "linear_scan":
        return _lower_linear_scan(node, env, backend)
    if op == "ssm_state_step":
        return _lower_ssm_state_step(node, env, backend)
    if op == "conv2d":
        return _lower_conv2d(node, env, backend)
    raise NotImplementedError(op)


def node_callable(node: Node, backend: str = "cpu",
                  bf16_partials: bool = False) -> Callable:
    """A pure callable computing ``node``'s value from positional operands.

    Operand order is ``node.inputs`` followed by every epilogue extra in
    epilogue order (duplicates kept); the returned callable carries that
    nid order as ``.operands``.  ``core.autodiff`` differentiates this —
    the primal half of the generic VJP rule — so it must lower the node
    EXACTLY as ``emit`` would: same impl, same tile, same epilogue chain.
    The node is replicated with dense operand ids so lowering never reads
    the originating graph."""
    k = len(node.inputs)
    repl = Node(nid=0, op=node.op, inputs=tuple(range(k)),
                ttype=node.ttype, attrs=dict(node.attrs),
                pdims=node.pdims, rdims=node.rdims)
    repl.schedule.impl = node.schedule.impl
    repl.schedule.tile = dict(node.schedule.tile)
    pos = k
    new_epi = []
    for fn, extras, at in node.epilogue:
        ids = tuple(range(pos, pos + len(extras)))
        pos += len(extras)
        new_epi.append((fn, ids, dict(at)))
    repl.epilogue = new_epi
    arity = pos

    def call(*vals):
        assert len(vals) == arity, (node.op, arity, len(vals))
        env = dict(enumerate(vals))
        return _lower_node(repl, env, {}, backend, bf16_partials)

    call.operands = tuple(node.inputs) + tuple(
        e for _, extras, _ in node.epilogue for e in extras)
    return call


def _multi_device_mesh():
    """The ambient mesh when it has >1 device (constraints are inert on a
    single device); probe shared with the pass pipeline."""
    from .passes import ambient_mesh
    m = ambient_mesh()
    return m if m is not None and m.size > 1 else None


def _apply_sharding(val, spec: tuple, mesh) -> Any:
    """Replay a captured sharding annotation as a real constraint under
    ``mesh``.  Degrades to a no-op when an axis the spec names is missing
    (a program somehow lowered off-mesh) or the constraint can't attach
    (outside a trace on some jax versions) — constraints are performance
    hints, numerics never depend on them."""
    names = set()
    for entry in spec:
        if entry is not None:
            names.update(entry if isinstance(entry, tuple) else (entry,))
    # an all-None spec is an explicit replication constraint — applied
    # like any other; only specs naming a MISSING axis degrade to no-ops
    if not names.issubset(set(mesh.axis_names)):
        return val
    from jax.sharding import NamedSharding, PartitionSpec as P
    try:
        return jax.lax.with_sharding_constraint(
            val, NamedSharding(mesh, P(*spec)))
    except (ValueError, TypeError) as e:
        # an all-None spec can be a bitwise guard (explicit replication
        # ahead of an out-projection), so a drop must not be silent —
        # warn at trace time and degrade
        warnings.warn(f"captured sharding constraint {spec} could not be "
                      f"applied under mesh {mesh.axis_names}: {e}")
        return val


def emit(g: TaskGraph, backend: str = "cpu",
         bf16_partials: bool = False) -> Callable[[dict], tuple]:
    """Compile the scheduled graph into a callable(inputs dict) -> outputs.

    Nodes carrying a ``sharding`` annotation (captured by the region
    tracer from ``shard_act``/``with_sharding_constraint`` calls) are
    re-constrained under the ambient mesh — the constraint a traced
    tensor would have received eagerly is replayed at lowering, so
    regions and GSPMD compose.  Off-mesh the annotations are inert."""
    order = g.topo_order()
    nodes = [g.nodes[nid] for nid in order]
    outputs = list(g.outputs)
    any_sharded = any(n.sharding for n in nodes)

    def run(inputs: dict) -> tuple:
        env: dict[int, Any] = {}
        mesh = _multi_device_mesh() if any_sharded else None
        for node in nodes:
            val = _lower_node(node, env, inputs, backend, bf16_partials)
            if node.sharding is not None and mesh is not None:
                val = _apply_sharding(val, node.sharding, mesh)
            env[node.nid] = val
        return tuple(env[o] for o in outputs)

    return run
