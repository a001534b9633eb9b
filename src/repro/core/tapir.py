"""Public op layer: models call these; each call builds a Task IR graph,
runs the pass pipeline (cached), and executes the lowered computation.

This is the integration point that makes the paper's technique a first-class
framework feature: every call site picks up the active ``TapirConfig`` —
``mode="tapir"`` (exposed libraries + fusion + late scheduling) or
``mode="opaque"`` (stock-XLA-style early heuristics) — so the paper's A/B is
a config switch, not a code fork.

Two execution regimes:

* **Per-op (eager)** — each public op builds, optimizes, caches and runs its
  own TaskGraph.  This was the only regime historically, and it is what
  stock XLA's library-call boundary looks like: no pass ever sees more than
  one op.
* **Region capture** — under ``tapir.region()`` / ``@tapir.parallel_region``
  the same public ops *trace* instead of executing: they return lazy
  :class:`TracedTensor` handles and append nodes to one region-wide
  TaskGraph.  At region exit the merged graph runs the full pass pipeline
  (CSE, added-GEMM fusion, shared-input fusion, epilogue fusion, late
  scheduling) across every op in the region, is emitted once, cached by
  structural signature, and executed under a single ``jax.jit``.  Residual
  adds, norms and sibling projections that live in *different* graphs in
  the per-op regime become one fused library op with an epilogue — the
  paper's cross-library-call claim at block scale.

Regions are also **stateful**: in-place buffer updates (KV caches, SSM
state) are first-class.  ``tapir.cache_write(buf, upd, starts)`` /
``tapir.cache_read(buf, starts, sizes)`` (and the jnp-style
``t.at[...].set(...)`` / basic ``t[...]`` indexing on traced tensors)
record ``dynamic_update_slice`` / ``dynamic_slice`` / ``index`` nodes.  A
write carries aliasing metadata (``Node.donates``): it is never CSE'd,
orders after every read of the pre-write buffer (anti-deps), and when the
aliased buffer is a region *input* the emitted jit donates it
(``donate_argnums``) so the cache updates in place — one decode step
becomes ONE region with zero per-step cache copies::

    @tapir.parallel_region
    def decode_block(p, x, ck, cv, pos, cos, sin):
        xn = rmsnorm(x, p["ln1"])                 # lifts as one node
        q, k, v = tapir.multi_linear(xn, [p["wq"], p["wk"], p["wv"]])
        ...
        ck = tapir.cache_write(ck, k, (0, pos, 0, 0))   # donates ck
        cv = tapir.cache_write(cv, v, (0, pos, 0, 0))   # donates cv
        o = _decode_attention(q, ck, cv, pos + 1)       # ordered after
        ...
        return x, ck, cv        # updated cache threads back to the caller
"""
from __future__ import annotations

import functools
import re
import threading
import time
import weakref
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.spans import span

from .ir import TaskGraph, TensorType
from .lowering import emit
from .passes import mesh_fingerprint, run_pipeline
from .schedule import CostModel, cost_model_for

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TapirConfig:
    mode: str = "tapir"                  # "tapir" | "opaque"
    backend: str = "auto"                # "auto" | "cpu" | "tpu"
    cost_model: Optional[CostModel] = None
    remat: str = "none"                  # "none" | "full" | "dots"
    ablate_serialization: bool = False
    # beyond-paper: emit k-sharded matmul partials in bf16 so TP
    # all-reduces move half the bytes (per-shard accumulation still runs in
    # the MXU's f32 accumulators); off for the paper-faithful baseline
    bf16_partials: bool = False
    # region capture: when False, ``tapir.region`` / ``parallel_region``
    # become no-ops and every op runs in the per-op regime (the A/B control
    # for the region_vs_per_op benchmark).
    regions: bool = True
    # impl-registry override: ((op_kind, impl_name), ...) pairs, e.g.
    # (("attention", "blockwise"),) — forces that candidate for every node
    # of the kind instead of the roofline argmin (tests/benchmarks that
    # need a specific lowered path).  Must stay a hashable tuple (part of
    # the compile-cache key).  Unknown or unavailable names raise at
    # schedule time.
    force_impl: Optional[tuple] = None
    # persistent program cache (L2): directory for the on-disk tier under
    # the in-memory caches.  None disables it.  A region program that
    # misses L1 probes L2 by content digest (graph signature + _cfg_key +
    # jax/jaxlib versions + pipeline salt) and, on a verified hit,
    # deserializes the AOT executable instead of compiling — a second
    # process on a warm directory compiles 0 programs.  NOT part of
    # ``_cfg_key``: where an artifact is stored never changes what it
    # computes.
    program_cache_dir: Optional[str] = None
    # "off" | "read" | "readwrite" — "read" probes but never publishes
    # (immutable fleet-shared cache), "readwrite" also publishes fresh
    # compiles.  Ignored while ``program_cache_dir`` is None.
    cache_mode: str = "readwrite"

    def resolved_backend(self) -> str:
        if self.backend != "auto":
            return self.backend
        return "tpu" if jax.default_backend() == "tpu" else "cpu"

    def resolved_cost_model(self) -> CostModel:
        if self.cost_model is not None:
            return self.cost_model
        return cost_model_for()


_tls = threading.local()


def get_config() -> TapirConfig:
    return getattr(_tls, "cfg", TapirConfig())


@contextmanager
def use(cfg: TapirConfig):
    prev = getattr(_tls, "cfg", None)
    _tls.cfg = cfg
    try:
        yield cfg
    finally:
        if prev is None:
            del _tls.cfg
        else:
            _tls.cfg = prev


# ---------------------------------------------------------------------------
# Graph build/execute machinery
# ---------------------------------------------------------------------------

_CACHE: dict[tuple, Callable] = {}
_CACHE_STATS = {
    "hits": 0, "misses": 0, "pipeline_s": 0.0,
    # region programs actually XLA-compiled this process (the warm-start
    # gate asserts this stays 0 on a populated cache directory)
    "compiled_programs": 0,
    # L2 (on-disk) tier outcomes, summed over every active cache dir
    "l2_hits": 0, "l2_misses": 0, "l2_quarantined": 0, "l2_writes": 0,
    # deserialized executables that failed at call time and were replaced
    # by a fresh compile (cache problem degraded to a compile, not a wrong
    # answer)
    "l2_fallbacks": 0,
    # ``parallel_region`` calls that traced their body instead of replaying
    # a recorded program: the host stall a warm loop should never pay
    "region_captures": 0,
}
#: optimized graphs by cache key — introspection for tests/benchmarks
_GRAPHS: dict[tuple, TaskGraph] = {}
#: per-program cache provenance (where each L1 entry came from), keyed
#: like ``_CACHE`` — surfaced by ``tapir.explain``
_PROVENANCE: dict[tuple, dict] = {}
#: ProgramDiskCache instances by (dir, mode) — shared so stats accumulate
#: and ``invalidate_mesh`` can purge every active disk tier
_L2_INSTANCES: dict[tuple, Any] = {}


def _tt(x) -> TensorType:
    return TensorType(tuple(x.shape), str(jnp.dtype(x.dtype)))


def _cfg_key(cfg: TapirConfig, backend: str) -> tuple:
    # The ambient mesh changes the fusion SHAPE (stacked vs concat QKV),
    # the sharding constraints captured on region nodes, and the meaning
    # of every mesh axis name those constraints reference — so compiled
    # artifacts must not leak between meshes.  The FULL fingerprint (axis
    # names + sizes) is the key component: fingerprinting only "has a
    # model axis" let two different TP meshes replay each other's
    # programs, executing constraints resolved for the wrong axis size.
    return (cfg.mode, backend, cfg.ablate_serialization,
            cfg.resolved_cost_model().name, cfg.bf16_partials,
            cfg.force_impl, mesh_fingerprint())


def _l2_for(cfg: TapirConfig):
    """Active on-disk tier for this config, or None when disabled."""
    if not cfg.program_cache_dir or cfg.cache_mode == "off":
        return None
    from repro.cache import ProgramDiskCache, enable_xla_disk_cache
    k = (cfg.program_cache_dir, cfg.cache_mode)
    l2 = _L2_INSTANCES.get(k)
    if l2 is None:
        l2 = ProgramDiskCache(cfg.program_cache_dir, cfg.cache_mode)
        _L2_INSTANCES[k] = l2
        if cfg.cache_mode == "readwrite":
            # warm the small compiles too (eager dispatches, outer jits)
            enable_xla_disk_cache()
    return l2


def _l2_digest(key: tuple) -> str:
    """Cross-process content digest of an L1 cache key: the canonical graph
    signature + full ``_cfg_key`` (mode/backend/cost model/force_impl/mesh
    fingerprint) the key already carries, salted with the jax/jaxlib
    versions and the repro pipeline version (``cache.PIPELINE_VERSION``) —
    an artifact compiled by a different compiler must never hit."""
    import jaxlib

    from repro.cache import FORMAT_VERSION, PIPELINE_VERSION, stable_digest
    return stable_digest(("tapir-program", FORMAT_VERSION, PIPELINE_VERSION,
                          jax.__version__, jaxlib.__version__, key))


def _positional_jit(emitted: Callable, g: TaskGraph):
    """(jitted, input names): jit the emitted fn positionally so
    ``donate_argnums`` can name exactly the cache inputs the graph's
    update-slice nodes donate — XLA then aliases input and output storage
    (no per-step cache copy)."""
    donated = g.donated_inputs()
    # jax assigns donated buffers to outputs greedily by aval, walking
    # outputs in order and consuming the first unmatched donated arg of
    # equal shape/dtype.  Region inputs are in first-USE order (forward
    # usage), outputs in return-tree order, and a training state has many
    # same-shaped leaves (a param and its two AdamW moments), so the raw
    # order would alias leaf A's buffer to leaf B's output — aliased, but
    # not IN PLACE.  Putting donated args last, sorted by the position of
    # the output that donates them, makes the greedy match exact:
    # each in-place update lands in its own buffer.
    out_pos = {}
    for i, onid in enumerate(g.outputs):
        d = g.nodes[onid].donates
        if d is not None and d not in out_pos:
            out_pos[d] = i
    don_sorted = sorted(donated, key=lambda d: out_pos.get(d, len(g.outputs)))
    nid2name = {nid: n for n, nid in g.inputs}
    don_names = [nid2name[d] for d in don_sorted]
    names = [n for n, _ in g.inputs if n not in set(don_names)] + don_names
    pos = tuple(range(len(names) - len(don_names), len(names)))

    def _positional(*argv):
        return emitted(dict(zip(names, argv)))

    # the jit's name is the compiled module's (``jit_tapir_slot_head``):
    # device traces and HLO dumps name each program by its region
    _positional.__name__ = _positional.__qualname__ = \
        "tapir_" + re.sub(r"\W", "_", g.name)
    return jax.jit(_positional, donate_argnums=pos), names


def _guarded_aot(compiled, names: list, fallback: Callable) -> Callable:
    """Dict-convention wrapper over an AOT executable with a one-shot
    degrade path: if the executable rejects a call (input layout/sharding
    drift the lazy jit would have absorbed by recompiling), swap in
    ``fallback()`` — a cache problem may cost a compile, never an answer.
    The retry is skipped if any argument was already consumed by donation
    (the failure happened mid-execution, not at dispatch)."""
    cell: dict[str, Any] = {}

    def fn(inputs: dict):
        if "call" in cell:
            return cell["call"](inputs)
        argv = [inputs[n] for n in names]
        try:
            return compiled(*argv)
        except Exception:
            if any(getattr(a, "is_deleted", lambda: False)() for a in argv):
                raise
            _CACHE_STATS["l2_fallbacks"] += 1
            cell["call"] = fallback()
            return cell["call"](inputs)

    return fn


def _l2_load(l2, digest: str, g: TaskGraph, cfg: TapirConfig, backend: str,
             key: tuple, example_inputs: dict) -> Optional[Callable]:
    """Verified L2 probe: deserialize the AOT executable and rebuild the
    replay callable from the sidecar (input-name order + recorded avals).
    Every failure past the probe quarantines the entry (in readwrite mode
    — a read-mode probe never mutates the shared store) and returns None —
    the caller recompiles."""
    q0 = l2.stats["quarantined"]
    got = l2.get(digest)
    _CACHE_STATS["l2_quarantined"] += l2.stats["quarantined"] - q0
    if got is None:
        _CACHE_STATS["l2_misses"] += 1
        return None
    payload, meta = got
    try:
        from jax.experimental.serialize_executable import \
            deserialize_and_load
        blob, in_tree, out_tree = payload
        names = [str(n) for n in meta["input_names"]]
        for n, (shape, dtype) in zip(names, meta["in_avals"]):
            v = example_inputs[n]
            if (tuple(shape) != tuple(v.shape)
                    or str(dtype) != str(jnp.dtype(v.dtype))):
                raise ValueError(f"aval mismatch on input {n}")
        compiled = deserialize_and_load(blob, in_tree, out_tree)
    except Exception:
        q1 = l2.stats["quarantined"]
        l2.quarantine(digest, "deserialize-failed")   # no-op in read mode
        _CACHE_STATS["l2_quarantined"] += l2.stats["quarantined"] - q1
        _CACHE_STATS["l2_misses"] += 1
        return None
    _CACHE_STATS["l2_hits"] += 1

    def fallback(g=g, cfg=cfg, backend=backend):
        # full clean recompile from the RAW captured graph (the pipeline
        # never ran on the hit path, so g is intact)
        g2 = run_pipeline(g, cfg.mode, cfg.resolved_cost_model(), backend,
                          ablate_serialization=cfg.ablate_serialization,
                          force_impl=cfg.force_impl)
        jitted, names2 = _positional_jit(
            emit(g2, backend, bf16_partials=cfg.bf16_partials), g2)
        _CACHE_STATS["compiled_programs"] += 1
        return lambda inputs: jitted(*[inputs[n] for n in names2])

    _PROVENANCE[key] = {"name": g.name, "source": "disk", "digest": digest,
                        "backend": backend,
                        "mesh_fingerprint": mesh_fingerprint()}
    return _guarded_aot(compiled, names, fallback)


def _l2_publish(l2, digest: str, compiled, g: TaskGraph, names: list,
                example_inputs: dict, backend: str) -> bool:
    """Serialize + transactionally publish a freshly compiled program with
    its provenance sidecar.  Publish failures are non-fatal: the compile
    already succeeded, the process just serves uncached."""
    try:
        from jax.experimental.serialize_executable import (
            deserialize_and_load, serialize)
        blob, in_tree, out_tree = serialize(compiled)
        # publish-time self-check: a blob we cannot load back is poison
        # for every future process — skip publishing it (backstop for
        # serialize-of-deserialized-executable bugs in the runtime)
        deserialize_and_load(blob, in_tree, out_tree)
        meta = {
            "graph_name": g.name,
            "backend": backend,
            "mesh_fingerprint": [list(p) for p in mesh_fingerprint()],
            "input_names": list(names),
            "in_avals": [[list(example_inputs[n].shape),
                          str(jnp.dtype(example_inputs[n].dtype))]
                         for n in names],
            "donated_inputs": [n for n, nid in g.inputs
                               if nid in g.donated_inputs()],
            "n_nodes": len(g.nodes),
            "impls": sorted({nd.schedule.impl for nd in g.nodes.values()
                             if nd.schedule.impl}),
            "created_at": time.time(),
        }
        ok = l2.put(digest, (blob, in_tree, out_tree), meta)
        if ok:
            _CACHE_STATS["l2_writes"] += 1
        return ok
    except Exception:
        return False


def _compile(g: TaskGraph, cfg: TapirConfig, backend: str,
             key: tuple, jit: bool = False,
             example_inputs: Optional[dict] = None) -> Callable:
    """pipeline + emit with cache bookkeeping (shared by per-op + region).

    For region programs (``jit=True``) called with concrete inputs, this is
    also the L2 integration point: probe the on-disk tier BEFORE running
    the pass pipeline (a verified hit skips pipeline + emit + XLA compile
    entirely), and publish fresh compiles after AOT-compiling against the
    example inputs.  Tracer inputs (region nested under an outer jit)
    bypass L2 — there is nothing concrete to AOT against."""
    t0 = time.perf_counter()
    l2 = None
    if jit and example_inputs is not None and not any(
            isinstance(v, jax.core.Tracer) for v in example_inputs.values()):
        l2 = _l2_for(cfg)
    digest = None
    if l2 is not None:
        digest = _l2_digest(key)
        raw_g = g
        fn = _l2_load(l2, digest, raw_g, cfg, backend, key, example_inputs)
        if fn is not None:
            _CACHE_STATS["pipeline_s"] += time.perf_counter() - t0
            _CACHE[key] = fn
            return fn
    g = run_pipeline(g, cfg.mode, cfg.resolved_cost_model(), backend,
                     ablate_serialization=cfg.ablate_serialization,
                     force_impl=cfg.force_impl)
    fn = emit(g, backend, bf16_partials=cfg.bf16_partials)
    if jit:
        _CACHE_STATS["compiled_programs"] += 1
        jitted, names = _positional_jit(fn, g)
        if l2 is not None:
            from repro.cache import suspend_xla_disk_cache
            argv = [example_inputs[n] for n in names]
            # compile OUTSIDE jax's persistent cache: an executable loaded
            # from it re-serializes to a broken blob on CPU, and L2 is the
            # canonical tier for region programs anyway
            with suspend_xla_disk_cache():
                compiled = jitted.lower(*argv).compile()
            published = _l2_publish(l2, digest, compiled, g, names,
                                    example_inputs, backend)
            _PROVENANCE[key] = {
                "name": g.name, "digest": digest, "backend": backend,
                "source": "compiled+published" if published else "compiled",
                "mesh_fingerprint": mesh_fingerprint()}
            # the lazy jit is the degrade path: it recompiles transparently
            # if a later call's input layout drifts from the AOT avals
            fn = _guarded_aot(
                compiled, names,
                lambda: lambda inputs: jitted(*[inputs[n] for n in names]))
        else:
            fn = lambda inputs: jitted(*[inputs[n] for n in names])  # noqa: E731
    _CACHE_STATS["pipeline_s"] += time.perf_counter() - t0
    _GRAPHS[key] = g
    _CACHE[key] = fn
    return fn


def _execute(op_key: tuple, build: Callable[[TaskGraph], None],
             inputs: dict[str, Any]) -> tuple:
    cfg = get_config()
    backend = cfg.resolved_backend()
    key = (op_key,) + _cfg_key(cfg, backend)
    fn = _CACHE.get(key)
    if fn is None:
        _CACHE_STATS["misses"] += 1
        g = TaskGraph(op_key[0])
        build(g)
        fn = _compile(g, cfg, backend, key)
    else:
        _CACHE_STATS["hits"] += 1
    return fn(inputs)


def trace_graph(op_key: tuple, build: Callable[[TaskGraph], None]) -> TaskGraph:
    """Build + optimize a graph without executing (for tests/inspection)."""
    cfg = get_config()
    g = TaskGraph(op_key[0])
    build(g)
    return run_pipeline(g, cfg.mode, cfg.resolved_cost_model(),
                        cfg.resolved_backend(),
                        ablate_serialization=cfg.ablate_serialization,
                        force_impl=cfg.force_impl)


# ---------------------------------------------------------------------------
# Region capture: TracedTensor + _Region
# ---------------------------------------------------------------------------


class TracedTensor:
    """Lazy handle to a node in an open region graph.

    Supports the tensor surface model code actually uses between op calls
    (arithmetic, ``reshape``, ``astype``); anything else coerces via
    ``__jax_array__``, which *flushes* the region segment (executes the
    pending graph) and degrades gracefully to a concrete array — capture is
    best-effort, correctness is unconditional."""

    __slots__ = ("_region", "nid", "ttype", "_concrete", "__weakref__")

    def __init__(self, region: "_Region", nid: Optional[int],
                 ttype: TensorType, concrete=None):
        self._region = region
        self.nid = nid
        self.ttype = ttype
        self._concrete = concrete

    # -- metadata --------------------------------------------------------
    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(self.ttype.shape)

    @property
    def dtype(self):
        return jnp.dtype(self.ttype.dtype)

    @property
    def ndim(self) -> int:
        return len(self.ttype.shape)

    def __repr__(self) -> str:
        state = "concrete" if self._concrete is not None else "lazy"
        return (f"TracedTensor({self.ttype.dtype}{list(self.ttype.shape)}, "
                f"{state})")

    # -- materialization -------------------------------------------------
    def jax(self):
        """Concrete value; flushes the region segment if still pending."""
        if self._concrete is None:
            if self._region.closed:
                raise RuntimeError("TracedTensor from an abandoned region")
            self._region.flush()
        return self._concrete

    def __jax_array__(self):
        return jnp.asarray(self.jax())

    # -- traced ops ------------------------------------------------------
    def _bin(self, other, fn: str, swap: bool = False):
        reg = self._region
        if reg.closed:
            a = self.jax()
            b = other.jax() if isinstance(other, TracedTensor) else other
            return _EAGER_BIN[fn](b, a) if swap else _EAGER_BIN[fn](a, b)
        a = reg.nid_of(self)
        b = reg.operand_nid(other, like=self)
        o_shape = np.broadcast_shapes(self.shape, _shape_of(other))
        o_dtype = _promote(self.ttype.dtype, other)
        out_t = TensorType(tuple(int(s) for s in o_shape), o_dtype)
        ins = (b, a) if swap else (a, b)
        nid = reg.g.add("ew", ins, out_t,
                        pdims=tuple(range(len(out_t.shape))), fn=fn)
        return reg.handle(nid)

    def __add__(self, other):
        return self._bin(other, "add")

    def __radd__(self, other):
        return self._bin(other, "add", swap=True)

    def __sub__(self, other):
        return self._bin(other, "sub")

    def __rsub__(self, other):
        return self._bin(other, "sub", swap=True)

    def __mul__(self, other):
        return self._bin(other, "mul")

    def __rmul__(self, other):
        return self._bin(other, "mul", swap=True)

    def __truediv__(self, other):
        return self._bin(other, "div")

    def __rtruediv__(self, other):
        return self._bin(other, "div", swap=True)

    def __neg__(self):
        reg = self._region
        if reg.closed:
            return -self.jax()
        nid = reg.g.add("ew", (reg.nid_of(self),), self.ttype,
                        pdims=tuple(range(self.ndim)), fn="neg")
        return reg.handle(nid)

    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = _resolve_reshape(self.shape, shape)
        reg = self._region
        if reg.closed:
            return jnp.reshape(self.jax(), shape)
        out_t = TensorType(shape, self.ttype.dtype)
        nid = reg.g.add("reshape", (reg.nid_of(self),), out_t,
                        pdims=tuple(range(len(shape))))
        return reg.handle(nid)

    def astype(self, dtype):
        dt = str(jnp.dtype(dtype))
        if dt == self.ttype.dtype:
            return self
        reg = self._region
        if reg.closed:
            return self.jax().astype(dtype)
        out_t = TensorType(self.shape, dt)
        nid = reg.g.add("convert", (reg.nid_of(self),), out_t,
                        pdims=tuple(range(self.ndim)))
        return reg.handle(nid)

    # -- indexing --------------------------------------------------------
    def __getitem__(self, item):
        """Basic static indexing (ints/slices/Ellipsis) stays lazy as an
        ``index`` node; integer-array indexing (traced or concrete) stays
        lazy as a ``gather`` node whose index operands are graph values;
        anything fancier (booleans, mixed forms) falls back through the
        flush escape hatch."""
        reg = self._region
        items = item if isinstance(item, tuple) else (item,)
        if not reg.closed and items and all(_is_int_array(s) for s in items):
            return gather(self, items)
        enc = _encode_index(item)
        if reg.closed or enc is None:
            return self.jax()[item]
        out = jax.eval_shape(lambda a: a[item],
                             jax.ShapeDtypeStruct(self.shape, self.dtype))
        out_t = TensorType(tuple(out.shape), str(out.dtype))
        nid = reg.g.add("index", (reg.nid_of(self),), out_t,
                        pdims=tuple(range(len(out_t.shape))), idx=enc)
        return reg.handle(nid)

    @property
    def at(self):
        """``x.at[idx].set(v)`` — the dynamic-update-slice subset of jnp's
        index-update protocol (int / scalar-array / full-slice indices)."""
        return _TracedAt(self)


class _TracedAt:
    __slots__ = ("_t",)

    def __init__(self, t: TracedTensor):
        self._t = t

    def __getitem__(self, idx):
        return _TracedAtIdx(self._t, idx if isinstance(idx, tuple) else (idx,))


class _TracedAtIdx:
    __slots__ = ("_t", "_idx")

    def __init__(self, t: TracedTensor, idx: tuple):
        self._t = t
        self._idx = idx

    def set(self, value, donate: bool = False):
        """In-bounds window set.  Out-of-bounds *dynamic* (scalar-array)
        starts follow ``lax.dynamic_update_slice`` clamp semantics, not
        jnp's drop — cache positions must stay within capacity.  Integer-
        ARRAY indices record a ``scatter`` node instead (jnp drop
        semantics: out-of-bounds updates are discarded)."""
        t = self._t
        if self._idx and all(_is_int_array(s) for s in self._idx):
            return scatter(t, self._idx, value, mode="set", donate=donate)
        idx = self._idx + (slice(None),) * (t.ndim - len(self._idx))
        starts, window = [], []
        for d, (s, extent) in enumerate(zip(idx, t.shape)):
            if isinstance(s, (bool, np.bool_)):
                return _at_set_fallback(t, self._idx, value)
            if isinstance(s, slice):
                if s != slice(None):
                    if not (s.step in (None, 1)):
                        return _at_set_fallback(t, self._idx, value)
                    lo, hi, _ = s.indices(extent)
                    if hi <= lo:
                        return _at_set_fallback(t, self._idx, value)
                    starts.append(lo)
                    window.append(hi - lo)
                else:
                    starts.append(0)
                    window.append(extent)
            elif isinstance(s, (int, np.integer)):
                # jnp index-update wraps negative indices; lax.dus clamps,
                # so normalize here
                starts.append(int(s) + extent if int(s) < 0 else int(s))
                window.append(1)
            elif _is_arraylike(s) and getattr(s, "ndim", None) == 0 \
                    and jnp.issubdtype(jnp.dtype(s.dtype), jnp.integer):
                starts.append(s)
                window.append(1)
            else:
                return _at_set_fallback(t, self._idx, value)
        return cache_write(t, value, tuple(starts), window=tuple(window),
                           donate=donate)

    def add(self, value, donate: bool = False):
        """Scatter-add at integer-array indices (the MoE dispatch form);
        other index shapes fall back to concrete jnp."""
        t = self._t
        if self._idx and all(_is_int_array(s) for s in self._idx):
            return scatter(t, self._idx, value, mode="add", donate=donate)
        v = value.jax() if isinstance(value, TracedTensor) else value
        return jnp.asarray(t.jax()).at[self._idx].add(v)


def _at_set_fallback(t: TracedTensor, idx, value):
    v = value.jax() if isinstance(value, TracedTensor) else value
    arr = jnp.asarray(t.jax())
    return arr.at[idx].set(v)


def _is_int_array(v) -> bool:
    """An integer index ARRAY operand (traced or concrete) — the gather/
    scatter index form, as opposed to basic ints/slices."""
    if isinstance(v, TracedTensor):
        return (v.ndim >= 1
                and jnp.issubdtype(jnp.dtype(v.ttype.dtype), jnp.integer))
    if isinstance(v, (bool, np.bool_)) or not hasattr(v, "dtype"):
        return False
    return (getattr(v, "ndim", 0) >= 1
            and jnp.issubdtype(jnp.dtype(v.dtype), jnp.integer))


def _encode_index(item) -> Optional[tuple]:
    """Hashable encoding of a basic index expression (None if unsupported)."""
    items = item if isinstance(item, tuple) else (item,)
    enc = []
    for s in items:
        if isinstance(s, (bool, np.bool_)):
            return None       # boolean index: mask semantics, fall back
        if isinstance(s, (int, np.integer)):
            enc.append(("i", int(s)))
        elif isinstance(s, slice):
            if not all(x is None or isinstance(x, (int, np.integer))
                       for x in (s.start, s.stop, s.step)):
                return None
            enc.append(("s", s.start, s.stop, s.step))
        elif s is Ellipsis:
            enc.append(("e",))
        elif s is None:
            enc.append(("n",))
        else:
            return None
    return tuple(enc)


def decode_index(enc: tuple) -> tuple:
    out = []
    for e in enc:
        if e[0] == "i":
            out.append(e[1])
        elif e[0] == "s":
            out.append(slice(e[1], e[2], e[3]))
        elif e[0] == "e":
            out.append(Ellipsis)
        else:
            out.append(None)
    return tuple(out)


_EAGER_BIN = {"add": lambda a, b: a + b, "sub": lambda a, b: a - b,
              "mul": lambda a, b: a * b, "div": lambda a, b: a / b}


def _shape_of(v) -> tuple:
    return tuple(getattr(v, "shape", ()))


def _promote(dtype: str, other) -> str:
    if isinstance(other, (int, float, bool)):
        return dtype   # python scalars are weakly typed, keep tensor dtype
    return str(jnp.promote_types(dtype, jnp.dtype(other.dtype)))


def _resolve_reshape(cur: tuple, shape: tuple) -> tuple[int, ...]:
    shape = tuple(int(s) for s in shape)
    if -1 in shape:
        known = int(np.prod([s for s in shape if s != -1])) or 1
        total = int(np.prod(cur)) if cur else 1
        shape = tuple(total // known if s == -1 else s for s in shape)
    return shape


def is_traced(x) -> bool:
    return isinstance(x, TracedTensor)


def in_region() -> bool:
    """True while a region capture is open on this thread — model code
    uses it to pick capture-stable paths (memoized rope tables, lifted
    composites) whose VALUES are bitwise-identical to the eager path."""
    return _active_region() is not None


def annotate_sharding(x, spec):
    """Record a sharding constraint on the node producing ``x``.

    ``spec`` is a PartitionSpec-like tuple (mesh axis name / tuple of
    names / None per output dim), already resolved against the ambient
    mesh by the caller (``repro.dist.shard_act``).  The annotation rides
    the node through every pass — CSE won't unify it with a differently-
    constrained twin, fusion moves it to whichever node takes over
    producing the value — and lowering replays it as
    ``jax.lax.with_sharding_constraint`` under the ambient mesh.  Safe to
    call on anything: non-traced values and closed regions pass through
    untouched, so the tracer never silently DROPS a constraint the per-op
    path would have applied.  An all-``None`` spec is still recorded — it
    is an explicit "replicated" constraint, which stops GSPMD from
    k-splitting a downstream contraction into partial sums whose
    all-reduce would reorder float adds (callers only annotate under an
    active multi-device mesh, so single-device keys never churn)."""
    if not isinstance(x, TracedTensor):
        return x
    spec = tuple(spec)
    reg = x._region
    if reg.closed or x.nid is None:
        return x
    reg.g.nodes[x.nid].sharding = spec
    return x


class _Region:
    """One open capture: a growing TaskGraph plus the concrete values bound
    to its input nodes.  ``flush`` executes the pending segment (the lazy-
    tensor escape hatch); ``finalize`` executes whatever handles are still
    alive at region exit (dead intermediates are never emitted)."""

    def __init__(self, name: str, cfg: TapirConfig):
        self.name = name
        self.cfg = cfg
        self.closed = False
        self.segments = 0
        #: where the last segment's program came from: "memory" (L1),
        #: "disk" (L2) or "compiled"
        self.source = "none"
        self.g = TaskGraph(name)
        self._inp_by_id: dict[int, int] = {}
        self._inp_vals: list[Any] = []
        self._handles: list[weakref.ref] = []

    # -- value -> nid ----------------------------------------------------
    def nid_of(self, x) -> int:
        if isinstance(x, TracedTensor):
            if x._concrete is not None:
                x = x._concrete         # arg wrapper or flushed handle
            elif x._region is self:
                return x.nid
            else:
                raise ValueError(
                    "TracedTensor used outside the region that created it")
        key = id(x)
        nid = self._inp_by_id.get(key)
        if nid is None:
            name = f"a{len(self._inp_vals)}"
            nid = self.g.add_input(name, _tt(x))
            self._inp_by_id[key] = nid
            self._inp_vals.append(x)    # also pins id(x)
        return nid

    def operand_nid(self, v, like: TracedTensor) -> int:
        if isinstance(v, (int, float, bool)):
            return self.g.add("const", (), TensorType((), like.ttype.dtype),
                              value=v)
        return self.nid_of(v)

    def handle(self, nid: int) -> TracedTensor:
        h = TracedTensor(self, nid, self.g.nodes[nid].ttype)
        self._handles.append(weakref.ref(h))
        return h

    def wrap(self, val) -> TracedTensor:
        """Wrap a concrete array as a passthrough handle (region arg)."""
        return TracedTensor(self, None, _tt(val), concrete=val)

    # -- execution -------------------------------------------------------
    def _pending(self) -> list[TracedTensor]:
        out, live = [], []
        for r in self._handles:
            h = r()
            if h is None:
                continue
            live.append(r)
            if h._concrete is None and h.nid is not None:
                out.append(h)
        self._handles = live
        return out

    def _run(self, outs: list[TracedTensor]) -> None:
        self.g.set_outputs([h.nid for h in outs])
        cfg, backend = self.cfg, self.cfg.resolved_backend()
        key = ("region", self.g.signature()) + _cfg_key(cfg, backend)
        inputs = {f"a{i}": v for i, v in enumerate(self._inp_vals)}
        fn = _CACHE.get(key)
        if fn is None:
            _CACHE_STATS["misses"] += 1
            compiled = _CACHE_STATS["compiled_programs"]
            fn = _compile(self.g, cfg, backend, key, jit=True,
                          example_inputs=inputs)
            self.source = "compiled" if \
                _CACHE_STATS["compiled_programs"] > compiled else "disk"
        else:
            _CACHE_STATS["hits"] += 1
            self.source = "memory"
        self._last_fn = fn
        results = fn(inputs)
        for h, r in zip(outs, results):
            h._concrete = r

    def flush(self) -> None:
        """Materialize the current segment; capture continues afresh."""
        pending = self._pending()
        if pending:
            self._run(pending)
        self.segments += 1
        self.g = TaskGraph(f"{self.name}#{self.segments}")
        self._inp_by_id = {}
        self._inp_vals = []

    def finalize(self) -> None:
        pending = self._pending()
        if pending:
            self._run(pending)
        self.closed = True

    def abandon(self) -> None:
        self.closed = True


def _region_stack() -> list:
    if not hasattr(_tls, "regions"):
        _tls.regions = []
    return _tls.regions


def _active_region() -> Optional[_Region]:
    stack = _region_stack()
    return stack[-1] if stack else None


@contextmanager
def region(name: str = "region"):
    """Context manager form of region capture.  Nested regions merge into
    the outermost one; with ``TapirConfig.regions=False`` this is a no-op
    (ops run per-op, the benchmark control).

    NOTE: the context-manager form re-traces its body every invocation
    (only compilation is deduped, via the graph-signature cache) — there is
    no call site to key a replay on.  Hot loops should prefer
    ``@parallel_region``, whose program cache skips tracing entirely on
    structurally repeated calls."""
    if _active_region() is not None or not get_config().regions:
        yield _active_region()
        return
    r = _Region(name, get_config())
    stack = _region_stack()
    stack.append(r)
    try:
        yield r
    except BaseException:
        r.abandon()
        raise
    finally:
        stack.pop()
    r.finalize()


#: call-site program cache: (body identity, arg treedef, leaf shapes,
#: config) -> a fast replay closure.  A hit skips region tracing entirely —
#: per call, a whole block costs ONE dict probe + ONE jitted call instead
#: of N per-op cache probes (or a full re-trace).  Values hold strong refs
#: to the body (and its __self__) so ids in the key can't be recycled.
_PROGRAMS: dict[tuple, tuple] = {}


def _leaf_key(v):
    if _is_arraylike(v):
        return ("arr", tuple(v.shape), str(jnp.dtype(v.dtype)))
    try:
        hash(v)
    except TypeError:
        return None
    return ("obj", v)


def parallel_region(fn=None, *, name: Optional[str] = None):
    """Decorator form: array arguments enter the region as lazy handles,
    the return pytree is materialized (one pipeline run + one ``jax.jit``
    call for the whole body) and returned as concrete arrays.  Structurally
    repeated calls replay through the program cache without re-tracing."""
    def deco(f):
        f_id = (id(getattr(f, "__func__", f)), id(getattr(f, "__self__", None)))

        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            if _active_region() is not None or not get_config().regions:
                return f(*args, **kwargs)
            cfg = get_config()
            leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
            lks = [_leaf_key(v) for v in leaves]
            # aliasing pattern: which leaves are the SAME array object.  The
            # region dedups aliased inputs into one graph input, so a replay
            # is only valid for calls with the identical aliasing.
            first_seen: dict[int, int] = {}
            alias = tuple(first_seen.setdefault(id(v), i)
                          if _is_arraylike(v) else -1
                          for i, v in enumerate(leaves))
            key = None
            if all(k is not None for k in lks):
                key = (f_id, treedef, tuple(lks), alias) + \
                    _cfg_key(cfg, cfg.resolved_backend())
                hit = _PROGRAMS.get(key)
                if hit is not None and hit[0] is getattr(f, "__func__", f):
                    _CACHE_STATS["hits"] += 1
                    return hit[2](leaves)

            _CACHE_STATS["region_captures"] += 1
            r = _Region(name or getattr(f, "__name__", "region"), cfg)
            with span("tapir.capture", region=r.name) as sp:
                out = _capture(f, r, key, leaves, treedef)
                sp.set_metadata(source=r.source)
            return out
        return wrapper
    return deco(fn) if fn is not None else deco


def _capture(f, r: _Region, key, leaves, treedef):
    """Trace ``f`` over ``leaves`` into ``r``, run its program (L1 hit, L2
    load or compile) and record a replay for ``key``; returns ``f``'s
    output with concrete arrays."""
    argpos = {}
    for i, v in enumerate(leaves):
        if _is_arraylike(v):
            argpos.setdefault(id(v), i)
    handles = [r.wrap(v) if _is_arraylike(v) else v for v in leaves]
    targs, tkwargs = jax.tree_util.tree_unflatten(treedef, handles)
    stack = _region_stack()
    stack.append(r)
    try:
        out = f(*targs, **tkwargs)
    except BaseException:
        r.abandon()
        raise
    finally:
        stack.pop()
    out_leaves, out_treedef = jax.tree_util.tree_flatten(out)
    pending = r._pending()
    if pending:
        r._run(pending)
    r.closed = True
    _maybe_cache_program(key, f, r, pending, out_leaves, out_treedef, argpos)
    return jax.tree_util.tree_map(
        lambda v: v._concrete if isinstance(v, TracedTensor) else v, out)


def _maybe_cache_program(key, f, r: _Region, pending, out_leaves,
                         out_treedef, argpos) -> None:
    """Record a replay closure for this call site if the capture was clean:
    no mid-region flush, every region input came from an argument leaf, and
    the output pytree is fully reconstructible from (results, arg leaves,
    hashable constants)."""
    if key is None or r.segments > 0 or not pending:
        return
    binding = []
    for v in r._inp_vals:
        j = argpos.get(id(v))
        if j is None:
            return          # closure-captured array: can't rebind safely
        binding.append(j)
    pend_idx = {id(h): i for i, h in enumerate(pending)}
    spec = []
    for lv in out_leaves:
        if isinstance(lv, TracedTensor):
            if id(lv) in pend_idx:
                spec.append(("res", pend_idx[id(lv)]))
            elif lv._concrete is not None and id(lv._concrete) in argpos:
                spec.append(("arg", argpos[id(lv._concrete)]))
            else:
                return
        elif _is_arraylike(lv) or isinstance(lv, jax.core.Tracer):
            return          # stray array/tracer output: don't capture it
        else:
            spec.append(("const", lv))
    fn_c, binding, spec = r._last_fn, tuple(binding), tuple(spec)

    def replay(leaves, fn_c=fn_c, binding=binding, spec=spec,
               out_treedef=out_treedef):
        results = fn_c({f"a{i}": leaves[j] for i, j in enumerate(binding)})
        outs = [results[i] if tag == "res"
                else leaves[i] if tag == "arg" else i
                for tag, i in spec]
        return jax.tree_util.tree_unflatten(out_treedef, outs)

    _PROGRAMS[key] = (getattr(f, "__func__", f),
                      getattr(f, "__self__", None), replay)


def _is_arraylike(v) -> bool:
    return (not isinstance(v, TracedTensor)
            and hasattr(v, "shape") and hasattr(v, "dtype"))


# ---------------------------------------------------------------------------
# Stateful buffer ops (KV cache / SSM state)
# ---------------------------------------------------------------------------


def _start_operands(reg: "_Region", starts) -> tuple[tuple, tuple]:
    """Split window starts into static ints and dynamic scalar operands.
    Returns (static_starts with None holes, nids of the dynamic holes)."""
    static, nids = [], []
    for s in starts:
        if isinstance(s, (int, np.integer)):
            static.append(int(s))
        else:
            static.append(None)
            nids.append(reg.nid_of(s))
    return tuple(static), tuple(nids)


def cache_write(buf, update, starts, window=None, donate: bool = True):
    """Window write with in-place intent: ``buf[starts:starts+window] = update``.

    Outside a region this is ``lax.dynamic_update_slice`` (the compiler
    handles aliasing under the caller's jit).  Inside a region it records a
    ``dynamic_update_slice`` node whose buffer input is *donated* (when
    ``donate=True``), so the region's own jit updates the cache storage in
    place — the caller must treat ``buf`` as consumed and use the returned
    tensor.  ``starts`` entries may be python ints or integer scalars
    (traced or concrete); ``window`` defaults to ``update.shape`` and must
    have ``buf.ndim`` entries."""
    reg = _active_region()
    if window is None:
        window = tuple(update.shape)
    if reg is None:
        u = jnp.asarray(update).astype(buf.dtype).reshape(window)
        return jax.lax.dynamic_update_slice(buf, u, tuple(starts))
    bi = reg.nid_of(buf)
    ui = reg.nid_of(update)
    b_t = reg.g.nodes[bi].ttype
    if len(window) != len(b_t.shape):
        raise ValueError(f"cache_write window rank {len(window)} != "
                         f"buffer rank {len(b_t.shape)}")
    static, dyn = _start_operands(reg, starts)
    nid = reg.g.add("dynamic_update_slice", (bi, ui) + dyn, b_t,
                    pdims=tuple(range(len(b_t.shape))),
                    donates=bi if donate else None,
                    static_starts=static, window=tuple(window))
    return reg.handle(nid)


def elemwise(x, fn: str):
    """Unary elementwise op by registry name ("silu", "tanh", ...).  Stays
    lazy on a traced tensor (one ``ew`` node — fusable into epilogues);
    eager otherwise."""
    if not isinstance(x, TracedTensor):
        from .lowering import _EW
        return _EW[fn](x)
    reg = x._region
    if reg.closed:
        from .lowering import _EW
        return _EW[fn](x.jax())
    nid = reg.g.add("ew", (reg.nid_of(x),), x.ttype,
                    pdims=tuple(range(x.ndim)), fn=fn)
    return reg.handle(nid)


def cache_read(buf, starts, sizes):
    """Window read: ``buf[starts : starts+sizes]`` (``lax.dynamic_slice``).
    Inside a region it stays lazy as a ``dynamic_slice`` node, ordered
    before any subsequent in-place write of the same buffer."""
    reg = _active_region()
    if reg is None:
        return jax.lax.dynamic_slice(buf, tuple(starts), tuple(sizes))
    bi = reg.nid_of(buf)
    b_t = reg.g.nodes[bi].ttype
    static, dyn = _start_operands(reg, starts)
    out_t = TensorType(tuple(int(s) for s in sizes), b_t.dtype)
    nid = reg.g.add("dynamic_slice", (bi,) + dyn, out_t,
                    pdims=tuple(range(len(out_t.shape))),
                    static_starts=static, sizes=tuple(int(s) for s in sizes))
    return reg.handle(nid)


def _index_operand(reg: "_Region", ix) -> int:
    """Graph value for one gather/scatter index operand.

    Traced tensors are already graph values; *numpy* integer arrays become
    ``const`` nodes (static index patterns like ``np.arange(slots)`` must
    not become region inputs — a fresh array id per call would disable the
    program-replay cache); device arrays become region inputs (rebindable
    when they are argument leaves)."""
    if isinstance(ix, TracedTensor):
        return reg.nid_of(ix)
    if isinstance(ix, (int, np.integer)):
        ix = np.asarray(ix, np.int32)
    if isinstance(ix, np.ndarray):
        ix = np.ascontiguousarray(ix, dtype=np.int32)
        return reg.g.add("const", (), TensorType(tuple(ix.shape),
                                                 str(ix.dtype)), value=ix)
    return reg.nid_of(ix)


def _index_sds(g: TaskGraph, nid: int) -> jax.ShapeDtypeStruct:
    t = g.nodes[nid].ttype
    return jax.ShapeDtypeStruct(tuple(t.shape), jnp.dtype(t.dtype))


def gather(src, indices):
    """Integer-array indexing with graph-value indices:
    ``src[i0, i1, ...]`` over the leading axes.

    Outside a region this is plain jnp advanced indexing.  Inside, it
    records ONE ``gather`` node whose index operands are graph values
    (traced router outputs, per-slot positions) — data-dependent reads
    stay in the region instead of flushing it."""
    indices = tuple(indices) if isinstance(indices, (tuple, list)) \
        else (indices,)
    reg = _active_region()
    if reg is None:
        return jnp.asarray(src)[tuple(jnp.asarray(i) for i in indices)]
    si = reg.nid_of(src)
    s_t = reg.g.nodes[si].ttype
    idx_nids = tuple(_index_operand(reg, i) for i in indices)
    out = jax.eval_shape(
        lambda s, *ix: s[ix],
        jax.ShapeDtypeStruct(tuple(s_t.shape), jnp.dtype(s_t.dtype)),
        *[_index_sds(reg.g, n) for n in idx_nids])
    out_t = TensorType(tuple(out.shape), str(out.dtype))
    nid = reg.g.add("gather", (si,) + idx_nids, out_t,
                    pdims=tuple(range(len(out_t.shape))),
                    n_idx=len(idx_nids))
    return reg.handle(nid)


def scatter(buf, indices, upd, mode: str = "set", donate: bool = True):
    """Write ``upd`` into ``buf`` at integer-array indices over the leading
    axes: ``buf.at[i0, i1, ...].set/add(upd, mode="drop")``.

    Same aliasing discipline as ``cache_write``: inside a region the
    ``scatter`` node's index operands are graph values, the node is never
    CSE'd, and with ``donate=True`` a region-input buffer is donated
    (per-slot KV-cache writes update in place) and the write orders after
    every read of the pre-write buffer (anti edges; a non-donating
    scatter is pure dataflow).  Out-of-bounds indices drop the update (jnp
    scatter semantics — a retired slot whose position ran past capacity
    writes nothing)."""
    indices = tuple(indices) if isinstance(indices, (tuple, list)) \
        else (indices,)
    reg = _active_region()
    if reg is None:
        b = jnp.asarray(buf)
        u = jnp.asarray(upd).astype(b.dtype)
        at = b.at[tuple(jnp.asarray(i) for i in indices)]
        return at.add(u, mode="drop") if mode == "add" \
            else at.set(u, mode="drop")
    bi = reg.nid_of(buf)
    b_t = reg.g.nodes[bi].ttype
    idx_nids = tuple(_index_operand(reg, i) for i in indices)
    ui = reg.nid_of(upd)
    nid = reg.g.add("scatter", (bi,) + idx_nids + (ui,), b_t,
                    pdims=tuple(range(len(b_t.shape))),
                    donates=bi if donate else None,
                    n_idx=len(idx_nids), mode=mode)
    return reg.handle(nid)


def scatter_new(shape, dtype, indices, upd, mode: str = "add"):
    """Scatter into a FRESH zeros buffer of ``shape``/``dtype`` (the MoE
    dispatch form: tokens scattered into ``[E, cap, d]``).  The zeros are
    synthesized inside the node (``zero_init``) — materializing them in
    model code would create a fresh region input every call and disable
    the program-replay cache."""
    indices = tuple(indices) if isinstance(indices, (tuple, list)) \
        else (indices,)
    dt = str(jnp.dtype(dtype))
    reg = _active_region()
    if reg is None:
        return scatter(jnp.zeros(tuple(shape), dt), indices, upd, mode=mode)
    idx_nids = tuple(_index_operand(reg, i) for i in indices)
    ui = reg.nid_of(upd)
    out_t = TensorType(tuple(int(s) for s in shape), dt)
    nid = reg.g.add("scatter", idx_nids + (ui,), out_t,
                    pdims=tuple(range(len(out_t.shape))),
                    n_idx=len(idx_nids), mode=mode, zero_init=True)
    return reg.handle(nid)


def lift(fn: Callable, *args, **static):
    """Record an opaque python composite as ONE region node (or one node
    per output for tuple-returning fns).

    ``fn(*arrays, **static)`` must be a pure jnp function of its array
    arguments (norms, RoPE, ...).  Outside a region this just calls ``fn``.
    Inside, the call becomes a ``pyfunc`` node: the region stays a single
    graph (single jit, CSE-able) without reimplementing fn's numerics in
    the IR.  A fn returning a flat tuple of arrays yields one ``pyfunc``
    node per element (each re-invokes fn and projects; XLA dedups the
    identical pure subcomputations under the region jit).  ``fn`` must be
    a module-level function (its identity is part of the graph signature /
    cache key)."""
    reg = _active_region()
    if reg is None:
        return fn(*args, **static)
    nids = [reg.nid_of(a) for a in args]
    sds = [jax.ShapeDtypeStruct(tuple(reg.g.nodes[n].ttype.shape),
                                jnp.dtype(reg.g.nodes[n].ttype.dtype))
           for n in nids]
    out = jax.eval_shape(functools.partial(fn, **static), *sds)
    if isinstance(out, jax.ShapeDtypeStruct):
        out_t = TensorType(tuple(out.shape), str(out.dtype))
        nid = reg.g.add("pyfunc", tuple(nids), out_t,
                        fn=fn, static=tuple(sorted(static.items())))
        return reg.handle(nid)
    if isinstance(out, (tuple, list)) and all(
            isinstance(o, jax.ShapeDtypeStruct) for o in out):
        handles = []
        for i, o in enumerate(out):
            out_t = TensorType(tuple(o.shape), str(o.dtype))
            nid = reg.g.add("pyfunc", tuple(nids), out_t,
                            fn=fn, static=tuple(sorted(static.items())),
                            out=i)
            handles.append(reg.handle(nid))
        return tuple(handles)
    raise TypeError(f"lift({fn.__name__}) must return an array or a flat "
                    f"tuple of arrays, got {type(out)}")


def capture_region(fn: Callable, *args, **kwargs) -> TaskGraph:
    """Trace ``fn`` under a region and return the RAW merged graph (outputs
    set, pipeline NOT run, nothing executed) — benchmark/pipeline-timing
    hook."""
    r = _Region(getattr(fn, "__name__", "region"), get_config())

    def lift_leaf(v):
        return r.wrap(v) if _is_arraylike(v) else v

    targs, tkwargs = jax.tree_util.tree_map(lift_leaf, (args, kwargs))
    stack = _region_stack()
    stack.append(r)
    try:
        out = fn(*targs, **tkwargs)
    finally:
        stack.pop()
    outs = [v for v in jax.tree_util.tree_leaves(out)
            if isinstance(v, TracedTensor) and v.nid is not None]
    r.g.set_outputs([h.nid for h in outs])
    r.abandon()
    return r.g


def trace_region(fn: Callable, *args, **kwargs) -> TaskGraph:
    """Like :func:`capture_region` but returns the OPTIMIZED graph."""
    cfg = get_config()
    g = capture_region(fn, *args, **kwargs)
    return run_pipeline(g, cfg.mode, cfg.resolved_cost_model(),
                        cfg.resolved_backend(),
                        ablate_serialization=cfg.ablate_serialization,
                        force_impl=cfg.force_impl)


# ---------------------------------------------------------------------------
# Shared graph builders (used by both the eager per-op path and the region
# tracer — one source of truth for each op's fork-join structure)
# ---------------------------------------------------------------------------


def _pd(t: TensorType) -> tuple[int, ...]:
    return tuple(range(len(t.shape)))


def _build_linear(g: TaskGraph, xi: int, wi: int, bi: Optional[int],
                  ri: Optional[int], activation: Optional[str]) -> int:
    x_t, w_t = g.nodes[xi].ttype, g.nodes[wi].ttype
    out_t = TensorType(tuple(x_t.shape[:-1]) + (w_t.shape[-1],), x_t.dtype)
    k = x_t.shape[-1]
    head = g.add("matmul", (xi, wi), out_t, pdims=_pd(out_t),
                 rdims=(("k", k),), k=k)
    if bi is not None:
        head = g.add("ew", (head, bi), out_t, pdims=_pd(out_t), fn="add")
    if activation is not None:
        head = g.add("ew", (head,), out_t, pdims=_pd(out_t), fn=activation)
    if ri is not None:
        head = g.add("ew", (head, ri), out_t, pdims=_pd(out_t), fn="add")
    return head


def _build_multi_linear(g: TaskGraph, xi: int, wis: Sequence[int],
                        bis: Sequence[Optional[int]]) -> list[int]:
    x_t = g.nodes[xi].ttype
    k = x_t.shape[-1]
    outs = []
    for wi, bi in zip(wis, bis):
        w_t = g.nodes[wi].ttype
        out_t = TensorType(tuple(x_t.shape[:-1]) + (w_t.shape[-1],), x_t.dtype)
        mm = g.add("matmul", (xi, wi), out_t, pdims=_pd(out_t),
                   rdims=(("k", k),), k=k)
        if bi is not None:
            mm = g.add("ew", (mm, bi), out_t, pdims=_pd(out_t), fn="add")
        outs.append(mm)
    return outs


def _build_gated_mlp(g: TaskGraph, xi: int, wgi: int, wui: int, wdi: int,
                     activation: str, gather_hidden: bool = False) -> int:
    x_t = g.nodes[xi].ttype
    f = g.nodes[wgi].ttype.shape[-1]
    hid_t = TensorType(tuple(x_t.shape[:-1]) + (f,), x_t.dtype)
    k = x_t.shape[-1]
    mg = g.add("matmul", (xi, wgi), hid_t, pdims=_pd(hid_t),
               rdims=(("k", k),), k=k)
    mu = g.add("matmul", (xi, wui), hid_t, pdims=_pd(hid_t),
               rdims=(("k", k),), k=k)
    act = g.add("ew", (mg,), hid_t, pdims=_pd(hid_t), fn=activation)
    # gather_hidden: replicate the hidden before the down-projection, for
    # callers that keep wd replicated (slot serving); GSPMD would otherwise
    # split wd's contraction and all-reduce partial sums in the activation
    # dtype, reordering float adds.  Inert off-mesh.
    prod = g.add("ew", (act, mu), hid_t, pdims=_pd(hid_t), fn="mul",
                 sharding=(None,) * len(hid_t.shape) if gather_hidden
                 else None)
    out_t = TensorType(tuple(x_t.shape[:-1]) +
                       (g.nodes[wdi].ttype.shape[-1],), x_t.dtype)
    return g.add("matmul", (prod, wdi), out_t, pdims=_pd(out_t),
                 rdims=(("k", f),), k=f)


def _build_attention(g: TaskGraph, qi: int, ki: int, vi: int,
                     biasi: Optional[int], causal: bool) -> int:
    q_t, k_t = g.nodes[qi].ttype, g.nodes[ki].ttype
    ins = [qi, ki, vi] + ([biasi] if biasi is not None else [])
    out_t = TensorType(tuple(q_t.shape), q_t.dtype)
    b, s, h, d = q_t.shape
    return g.add("attention", tuple(ins), out_t, pdims=(0, 1, 2),
                 rdims=(("kv", k_t.shape[1]),),
                 causal=causal, q_shape=(b, s, h, d), kv_len=k_t.shape[1],
                 kv_heads=k_t.shape[2])


def _build_paged_attention(g: TaskGraph, qi: int, ki: int, vi: int,
                           ptabi: int, leni: int) -> int:
    q_t, k_t = g.nodes[qi].ttype, g.nodes[ki].ttype
    pps = g.nodes[ptabi].ttype.shape[-1]
    out_t = TensorType(tuple(q_t.shape), q_t.dtype)
    b, s, h, d = q_t.shape
    return g.add("paged_attention", (qi, ki, vi, ptabi, leni), out_t,
                 pdims=(0, 1, 2), rdims=(("kv", pps * k_t.shape[1]),),
                 q_shape=(b, s, h, d), page_len=k_t.shape[1], pps=pps,
                 kv_heads=k_t.shape[2])


def _build_ssm_state_step(g: TaskGraph, hi: int, ai: int, ui: int, bi: int,
                          ci: int) -> tuple[int, int]:
    """Two nodes over the same operands: ``out=0`` is y [S,H,P] f32,
    ``out=1`` the state rows, written in place (the node donates ``h``,
    and orders after the y node, which reads it)."""
    h_t, u_t = g.nodes[hi].ttype, g.nodes[ui].ttype
    s, h, p = u_t.shape
    n = h_t.shape[2]
    ins = (hi, ai, ui, bi, ci)
    kw = dict(rdims=(("state", n),), dims=(s, h, p, n))
    y = g.add("ssm_state_step", ins, TensorType((s, h, p), "float32"),
              pdims=(0, 1, 2), out=0, **kw)
    hn = g.add("ssm_state_step", ins, h_t, pdims=(0, 1, 2, 3), donates=hi,
               out=1, **kw)
    return y, hn


def _build_wkv_scan(g: TaskGraph, qi: int, ki: int, vi: int, wi: int,
                    ui: Optional[int]) -> int:
    q_t, v_t = g.nodes[qi].ttype, g.nodes[vi].ttype
    ins = [qi, ki, vi, wi] + ([ui] if ui is not None else [])
    out_t = TensorType(tuple(v_t.shape), v_t.dtype)
    return g.add("linear_scan", tuple(ins), out_t, pdims=(0, 2),
                 rdims=(("seq", q_t.shape[1]),), seq=q_t.shape[1],
                 variant="rwkv6" if ui is not None else "gla")


def _build_expert_mlp(g: TaskGraph, xi: int, wgi: int, wui: int, wdi: int,
                      activation: str, held: Optional[tuple] = None) -> int:
    E, C, d = g.nodes[xi].ttype.shape
    dt = g.nodes[xi].ttype.dtype
    f = g.nodes[wgi].ttype.shape[-1]
    hid_t = TensorType((E, C, f), dt)
    # ``held``: (lo, hi, routed) — which of the router's experts these
    # GEMMs hold; recorded on them for ``explain``
    kw = {} if held is None else {"experts_held": tuple(held)}
    mg = g.add("matmul", (xi, wgi), hid_t, pdims=(0, 1, 2),
               rdims=(("k", d),), k=d, **kw)
    mu = g.add("matmul", (xi, wui), hid_t, pdims=(0, 1, 2),
               rdims=(("k", d),), k=d, **kw)
    act = g.add("ew", (mg,), hid_t, pdims=(0, 1, 2), fn=activation)
    prod = g.add("ew", (act, mu), hid_t, pdims=(0, 1, 2), fn="mul")
    out_t = TensorType((E, C, d), dt)
    return g.add("matmul", (prod, wdi), out_t, pdims=(0, 1, 2),
                 rdims=(("k", f),), k=f, **kw)


def _build_lstm_step(g: TaskGraph, xi: int, hi: int, ci: int, Wi: int,
                     bi: int) -> tuple[int, int]:
    x_t, h_t = g.nodes[xi].ttype, g.nodes[hi].ttype
    W_t, b_t0 = g.nodes[Wi].ttype, g.nodes[bi].ttype
    xd, hd = x_t.shape[-1], h_t.shape[-1]
    B = x_t.shape[0]
    gate_t = TensorType((B, hd), x_t.dtype)
    Wx_t = TensorType((xd, hd), W_t.dtype)
    Wh_t = TensorType((hd, hd), W_t.dtype)
    bg_t = TensorType((hd,), b_t0.dtype)
    gates = []
    for gi in range(4):
        wx = g.add("slice", (Wi,), TensorType((xd, 4 * hd), W_t.dtype),
                   pdims=(0, 1), axis=0, start=0, limit=xd)
        wx = g.add("slice", (wx,), Wx_t, pdims=(0, 1), axis=1,
                   start=gi * hd, limit=(gi + 1) * hd)
        wh = g.add("slice", (Wi,), TensorType((hd, 4 * hd), W_t.dtype),
                   pdims=(0, 1), axis=0, start=xd, limit=xd + hd)
        wh = g.add("slice", (wh,), Wh_t, pdims=(0, 1), axis=1,
                   start=gi * hd, limit=(gi + 1) * hd)
        bg = g.add("slice", (bi,), bg_t, pdims=(0,), axis=0,
                   start=gi * hd, limit=(gi + 1) * hd)
        mx = g.add("matmul", (xi, wx), gate_t, pdims=(0, 1),
                   rdims=(("k", xd),), k=xd)
        mh = g.add("matmul", (hi, wh), gate_t, pdims=(0, 1),
                   rdims=(("k", hd),), k=hd)
        s = g.add("ew", (mx, mh), gate_t, pdims=(0, 1), fn="add")
        s = g.add("ew", (s, bg), gate_t, pdims=(0, 1), fn="add")
        gates.append(s)
    i_g = g.add("ew", (gates[0],), gate_t, pdims=(0, 1), fn="sigmoid")
    f_g = g.add("ew", (gates[1],), gate_t, pdims=(0, 1), fn="sigmoid")
    g_g = g.add("ew", (gates[2],), gate_t, pdims=(0, 1), fn="tanh")
    o_g = g.add("ew", (gates[3],), gate_t, pdims=(0, 1), fn="sigmoid")
    fc = g.add("ew", (f_g, ci), gate_t, pdims=(0, 1), fn="mul")
    ig = g.add("ew", (i_g, g_g), gate_t, pdims=(0, 1), fn="mul")
    c2 = g.add("ew", (fc, ig), gate_t, pdims=(0, 1), fn="add")
    tc = g.add("ew", (c2,), gate_t, pdims=(0, 1), fn="tanh")
    h2 = g.add("ew", (o_g, tc), gate_t, pdims=(0, 1), fn="mul")
    return h2, c2


def _build_conv2d(g: TaskGraph, xi: int, ki: int, bi: Optional[int],
                  strides: tuple, padding: str,
                  activation: Optional[str]) -> int:
    x_t, k_t = g.nodes[xi].ttype, g.nodes[ki].ttype
    B, H, Wd, _ = x_t.shape
    kh, kw, cin, co = k_t.shape
    if padding == "SAME":
        ho, wo = -(-H // strides[0]), -(-Wd // strides[1])
    else:
        ho = (H - kh) // strides[0] + 1
        wo = (Wd - kw) // strides[1] + 1
    out_t = TensorType((B, ho, wo, co), x_t.dtype)
    head = g.add("conv2d", (xi, ki), out_t, pdims=(0, 1, 2, 3),
                 rdims=(("k", kh * kw * cin),),
                 strides=strides, padding=padding, k_elems=kh * kw * cin)
    if bi is not None:
        head = g.add("ew", (head, bi), out_t, pdims=(0, 1, 2, 3), fn="add")
    if activation:
        head = g.add("ew", (head,), out_t, pdims=(0, 1, 2, 3), fn=activation)
    return head


# ---------------------------------------------------------------------------
# Ops
# ---------------------------------------------------------------------------


def linear(x, w, b=None, activation: Optional[str] = None, residual=None):
    """y = act(x @ w + b) (+ residual).  Library GEMM with open epilogue."""
    reg = _active_region()
    if reg is not None:
        head = _build_linear(reg.g, reg.nid_of(x), reg.nid_of(w),
                             None if b is None else reg.nid_of(b),
                             None if residual is None else reg.nid_of(residual),
                             activation)
        return reg.handle(head)

    sig = ("linear", x.shape, str(x.dtype), w.shape, str(w.dtype),
           b is not None, activation, residual is not None)
    inputs = {"x": x, "w": w}
    if b is not None:
        inputs["b"] = b
    if residual is not None:
        inputs["res"] = residual

    def build(g: TaskGraph):
        xi = g.add_input("x", _tt(x))
        wi = g.add_input("w", _tt(w))
        bi = g.add_input("b", _tt(b)) if b is not None else None
        ri = g.add_input("res", _tt(residual)) if residual is not None else None
        g.set_outputs([_build_linear(g, xi, wi, bi, ri, activation)])

    return _execute(sig, build, inputs)[0]


def multi_linear(x, ws: Sequence, bs: Optional[Sequence] = None):
    """k projections of the same activation (Q,K,V[,G]).  In tapir mode the
    shared-input fusion pass turns these into ONE wide GEMM + slices."""
    bs = list(bs) if bs is not None else [None] * len(ws)
    reg = _active_region()
    if reg is not None:
        outs = _build_multi_linear(
            reg.g, reg.nid_of(x), [reg.nid_of(w) for w in ws],
            [None if b is None else reg.nid_of(b) for b in bs])
        return tuple(reg.handle(o) for o in outs)

    sig = ("multi_linear", x.shape, str(x.dtype),
           tuple(w.shape for w in ws), tuple(b is not None for b in bs))
    inputs = {"x": x}
    for i, w in enumerate(ws):
        inputs[f"w{i}"] = w
    for i, b in enumerate(bs):
        if b is not None:
            inputs[f"b{i}"] = b

    def build(g: TaskGraph):
        xi = g.add_input("x", _tt(x))
        wis = [g.add_input(f"w{i}", _tt(w)) for i, w in enumerate(ws)]
        bis = [g.add_input(f"b{i}", _tt(b)) if b is not None else None
               for i, b in enumerate(bs)]
        g.set_outputs(_build_multi_linear(g, xi, wis, bis))

    return _execute(sig, build, inputs)


def gated_mlp(x, w_gate, w_up, w_down, activation: str = "silu",
              gather_hidden: bool = False):
    """SwiGLU MLP: down( act(x@w_gate) * (x@w_up) ).  Gate/up share input ->
    fused into one GEMM; the mul and the down-proj epilogue fuse too.
    ``gather_hidden`` replicates the hidden under a mesh, so a replicated
    ``w_down`` contracts it whole on every device."""
    reg = _active_region()
    if reg is not None:
        out = _build_gated_mlp(reg.g, reg.nid_of(x), reg.nid_of(w_gate),
                               reg.nid_of(w_up), reg.nid_of(w_down),
                               activation, gather_hidden)
        return reg.handle(out)

    sig = ("gated_mlp", x.shape, str(x.dtype), w_gate.shape, w_down.shape,
           activation, gather_hidden)
    inputs = {"x": x, "wg": w_gate, "wu": w_up, "wd": w_down}

    def build(g: TaskGraph):
        xi = g.add_input("x", _tt(x))
        wg = g.add_input("wg", _tt(w_gate))
        wu = g.add_input("wu", _tt(w_up))
        wd = g.add_input("wd", _tt(w_down))
        g.set_outputs([_build_gated_mlp(g, xi, wg, wu, wd, activation,
                                        gather_hidden)])

    return _execute(sig, build, inputs)[0]


def attention(q, k, v, causal: bool = False, bias=None):
    """Multi-head attention library op.  q:[B,Sq,Hq,D] k/v:[B,Skv,Hkv,D].
    GQA is implicit (Hq a multiple of Hkv)."""
    reg = _active_region()
    if reg is not None:
        out = _build_attention(reg.g, reg.nid_of(q), reg.nid_of(k),
                               reg.nid_of(v),
                               None if bias is None else reg.nid_of(bias),
                               causal)
        return reg.handle(out)

    sig = ("attention", q.shape, k.shape, str(q.dtype), causal, bias is not None)
    inputs = {"q": q, "k": k, "v": v}
    if bias is not None:
        inputs["bias"] = bias

    def build(g: TaskGraph):
        qi = g.add_input("q", _tt(q))
        ki = g.add_input("k", _tt(k))
        vi = g.add_input("v", _tt(v))
        bi = g.add_input("bias", _tt(bias)) if bias is not None else None
        g.set_outputs([_build_attention(g, qi, ki, vi, bi, causal)])

    return _execute(sig, build, inputs)[0]


def paged_attention(q, k_pool, v_pool, ptab, lengths):
    """Decode attention over a page pool.  q: [B,1,H,D]; k_pool/v_pool:
    [P,page_len,Hkv,D]; ptab: int32[B,pps], slot b's logical page j is
    pool page ``ptab[b, j]``; lengths: int[B], keys at positions >=
    lengths[b] are masked.  The registry binds the Pallas kernel that
    reads the live pages in place, or the gathered-view composite."""
    reg = _active_region()
    if reg is not None:
        out = _build_paged_attention(reg.g, reg.nid_of(q), reg.nid_of(k_pool),
                                     reg.nid_of(v_pool), reg.nid_of(ptab),
                                     reg.nid_of(lengths))
        return reg.handle(out)

    lengths = jnp.asarray(lengths, jnp.int32)
    sig = ("paged_attention", q.shape, k_pool.shape, ptab.shape,
           str(q.dtype), str(k_pool.dtype))
    inputs = {"q": q, "k": k_pool, "v": v_pool, "ptab": ptab,
              "lengths": lengths}

    def build(g: TaskGraph):
        ids = [g.add_input(n, _tt(v)) for n, v in inputs.items()]
        g.set_outputs([_build_paged_attention(g, *ids)])

    return _execute(sig, build, inputs)[0]


def ssm_state_step(h, a, u, bm, cm):
    """One-position SSM state update over packed state rows (decode).
    h: f32[R, H/g, N, g*P] (``kernels.ssm_state_step.ref``'s layout);
    a: f32[S, H] decay; u: f32[S, H, P], dt * x; bm, cm: f32[S, N].  For
    rows s < S: ``h <- a h + u (x) B``, ``y = C . h``; rows past S are left
    as they are.  Returns (y f32[S, H, P], h).  The registry binds the
    Pallas kernel that updates the rows in place, or the jnp composite."""
    reg = _active_region()
    if reg is not None:
        y, hn = _build_ssm_state_step(reg.g, *(reg.nid_of(t)
                                               for t in (h, a, u, bm, cm)))
        return reg.handle(y), reg.handle(hn)

    sig = ("ssm_state_step", h.shape, u.shape, bm.shape)
    inputs = {"h": h, "a": a, "u": u, "bm": bm, "cm": cm}

    def build(g: TaskGraph):
        ids = [g.add_input(n, _tt(v)) for n, v in inputs.items()]
        g.set_outputs(list(_build_ssm_state_step(g, *ids)))

    y, hn = _execute(sig, build, inputs)
    return y, hn


def wkv_scan(q, k, v, w, u=None):
    """Gated linear-attention scan:  S_t = diag(w_t) S_{t-1} + k_t^T v_t,
    o_t = q_t S_t (+ u * (q_t . k_t) v_t bonus when u given — RWKV6).
    q/k/w: [B,S,H,Dk], v: [B,S,H,Dv], u: [H,Dk] or None."""
    reg = _active_region()
    if reg is not None:
        out = _build_wkv_scan(reg.g, reg.nid_of(q), reg.nid_of(k),
                              reg.nid_of(v), reg.nid_of(w),
                              None if u is None else reg.nid_of(u))
        return reg.handle(out)

    sig = ("wkv_scan", q.shape, v.shape, str(q.dtype), u is not None)
    inputs = {"q": q, "k": k, "v": v, "w": w}
    if u is not None:
        inputs["u"] = u

    def build(g: TaskGraph):
        ins = [g.add_input(n, _tt(t)) for n, t in
               (("q", q), ("k", k), ("v", v), ("w", w))]
        ui = g.add_input("u", _tt(u)) if u is not None else None
        g.set_outputs([_build_wkv_scan(g, *ins, ui)])

    return _execute(sig, build, inputs)[0]


def expert_mlp(xe, w_gate, w_up, w_down, activation: str = "silu",
               held: Optional[tuple] = None):
    """Batched expert FFN: xe [E,C,d] x w [E,d,f].  In opaque mode the
    batched GEMMs lower to per-expert library calls; in tapir mode a single
    grouped einsum with fused epilogues.  ``held`` (lo, hi, routed) names
    which of the router's experts these are, for ``explain``."""
    reg = _active_region()
    if reg is not None:
        out = _build_expert_mlp(reg.g, reg.nid_of(xe), reg.nid_of(w_gate),
                                reg.nid_of(w_up), reg.nid_of(w_down),
                                activation, held)
        return reg.handle(out)

    sig = ("expert_mlp", xe.shape, str(xe.dtype), w_gate.shape, w_down.shape,
           activation) + (() if held is None else (held,))
    inputs = {"x": xe, "wg": w_gate, "wu": w_up, "wd": w_down}

    def build(g: TaskGraph):
        xi = g.add_input("x", _tt(xe))
        wg = g.add_input("wg", _tt(w_gate))
        wu = g.add_input("wu", _tt(w_up))
        wd = g.add_input("wd", _tt(w_down))
        g.set_outputs([_build_expert_mlp(g, xi, wg, wu, wd, activation,
                                         held)])

    return _execute(sig, build, inputs)[0]


def lstm_step(x, h, c, W, b):
    """One LSTM cell step.  W: [xd+hd, 4*hd] (i,f,g,o), b: [4*hd].

    The graph is built the way stock XLA emitted it — EIGHT separate GEMMs
    (4 gates x {x,h} slices of W) plus adds — exposing all logical
    parallelism.  In tapir mode the pipeline (CSE + added-GEMM fusion +
    shared-input fusion) collapses them into ONE GEMM; in opaque mode they
    stay eight isolated library calls.  Returns (h', c')."""
    reg = _active_region()
    if reg is not None:
        h2, c2 = _build_lstm_step(reg.g, reg.nid_of(x), reg.nid_of(h),
                                  reg.nid_of(c), reg.nid_of(W), reg.nid_of(b))
        return reg.handle(h2), reg.handle(c2)

    sig = ("lstm_step", x.shape, str(x.dtype), W.shape)
    inputs = {"x": x, "h": h, "c": c, "W": W, "b": b}

    def build(g: TaskGraph):
        xi = g.add_input("x", _tt(x))
        hi = g.add_input("h", _tt(h))
        ci = g.add_input("c", _tt(c))
        Wi = g.add_input("W", _tt(W))
        bi = g.add_input("b", _tt(b))
        g.set_outputs(list(_build_lstm_step(g, xi, hi, ci, Wi, bi)))

    h2, c2 = _execute(sig, build, inputs)
    return h2, c2


def conv2d(x, kern, b=None, strides=(1, 1), padding="SAME",
           activation: Optional[str] = None):
    """NHWC conv library op with open epilogue."""
    reg = _active_region()
    if reg is not None:
        out = _build_conv2d(reg.g, reg.nid_of(x), reg.nid_of(kern),
                            None if b is None else reg.nid_of(b),
                            tuple(strides), padding, activation)
        return reg.handle(out)

    sig = ("conv2d", x.shape, str(x.dtype), kern.shape, strides, padding,
           b is not None, activation)
    inputs = {"x": x, "k": kern}
    if b is not None:
        inputs["b"] = b

    def build(g: TaskGraph):
        xi = g.add_input("x", _tt(x))
        ki = g.add_input("k", _tt(kern))
        bi = g.add_input("b", _tt(b)) if b is not None else None
        g.set_outputs([_build_conv2d(g, xi, ki, bi, tuple(strides), padding,
                                     activation)])

    return _execute(sig, build, inputs)[0]


# ---------------------------------------------------------------------------
# Structured control flow ("loop spawning" decisions)
# ---------------------------------------------------------------------------


def scan_layers(body: Callable, stacked_params, x, unroll_hint: Optional[int] = None):
    """Run ``x = body(params_i, x)`` over a stacked layer pytree.

    Scan-vs-unroll is a cost-model decision (``unroll_max_trip``), not a
    mode one: shallow stacks unroll in EVERY mode, deep stacks ``lax.scan``
    (one lowering of the block; XLA pipelines it).  Keeping the iteration
    structure identical across modes matters for bits — XLA compiles a
    scan body in its own fusion context, so a scanned stack and the same
    stack unrolled differ in the last ulp under bf16, and the per-op path
    would silently stop being bitwise-comparable to a region capture
    (which always unrolls into the task graph).  The config's remat
    policy wraps the body either way — ``jax.checkpoint`` makes each
    layer's backward a transpose unit, the association the captured
    step's per-node VJP reproduces."""
    cfg = get_config()
    L = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

    leaves = jax.tree_util.tree_leaves(stacked_params)
    if _active_region() is not None and (
            isinstance(x, TracedTensor)
            or any(isinstance(l, TracedTensor) for l in leaves)):
        # region capture: unroll into the task graph.  ``lax.scan`` on a
        # TracedTensor would coerce via ``__jax_array__`` and flush the
        # region (splitting the capture); the unrolled python loop keeps
        # every layer in ONE graph, so CSE/fusion see across layers —
        # and, for a captured training step, across the fwd/bwd boundary.
        # ``a[i]`` on a traced leaf is an "index" node; semantics match
        # the scan exactly (same body, same order, fixed trip count).
        for i in range(L):
            p_i = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
            x = body(p_i, x)
        return x

    fn = body
    if cfg.remat == "full":
        fn = jax.checkpoint(body)
    elif cfg.remat == "dots":
        fn = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    if L <= max(cfg.resolved_cost_model().unroll_max_trip, unroll_hint or 0):
        for i in range(L):
            p_i = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
            x = fn(p_i, x)
        return x

    def step(carry, p_i):
        return fn(p_i, carry), None

    out, _ = jax.lax.scan(step, x, stacked_params)
    return out


def cache_stats() -> dict:
    return dict(_CACHE_STATS, size=len(_CACHE))


def cached_graphs() -> dict[tuple, TaskGraph]:
    """Optimized TaskGraphs by cache key (introspection for tests/bench)."""
    return dict(_GRAPHS)


def explain(g: Optional[TaskGraph] = None) -> str:
    """Human-readable schedule report: per library node, the impl the
    registry chose, the full candidate cost table, tiles, and schedule
    notes (``TaskGraph.dump_schedule``).  With no argument, reports every
    graph compiled so far this process (the ``cached_graphs()`` table) —
    run your model once, then print ``tapir.explain()`` to see why each
    attention/GEMM/scan lowered the way it did, no debugger needed."""
    if g is not None:
        return g.dump_schedule()
    if not _GRAPHS and not _PROVENANCE:
        return "(no compiled graphs yet — run something under tapir first)"
    parts = [gr.dump_schedule() for gr in _GRAPHS.values()]
    grad_graphs = [gr for gr in _GRAPHS.values()
                   if getattr(gr, "grad_meta", None)]
    if grad_graphs:
        lines = ["== gradient programs =="]
        for gr in grad_graphs:
            m = gr.grad_meta
            lines.append(
                f"  {gr.name}: {m['n_fwd']} fwd nodes, {m['n_bwd']} bwd "
                f"nodes; remat {m['remat']['store']} stored / "
                f"{m['remat']['recompute']} recomputed "
                f"({m['bytes_stored']} B stored vs "
                f"{m['bytes_recomputed']} B recomputed)")
            for nid in sorted(gr.nodes):
                node = gr.nodes[nid]
                if node.schedule.remat:
                    lines.append(f"    %{nid} {node.op}: "
                                 f"{node.schedule.remat}")
        parts.append("\n".join(lines))
    if _PROVENANCE:
        lines = ["== program cache provenance =="]
        for info in _PROVENANCE.values():
            lines.append(
                f"  {info['name']}: {info['source']} "
                f"digest={info['digest'][:12]} backend={info['backend']}")
        parts.append("\n".join(lines))
    return "\n".join(parts)


def program_cache(cfg: Optional[TapirConfig] = None):
    """The active on-disk L2 ``ProgramDiskCache`` for ``cfg`` (default: the
    current config), or None when disabled.  Exposes explicit maintenance
    entry points — ``clear()`` and ``invalidate(fingerprint)`` — that the
    in-memory ``clear_cache()`` deliberately does NOT call: clearing L1 is
    a per-process action, purging L2 is a store-wide one."""
    return _l2_for(cfg or get_config())


def clear_cache() -> None:
    """Drop the in-memory (L1) tier only.  The on-disk L2 store is
    untouched — use ``program_cache().clear()`` / ``.invalidate(fp)`` for
    store-wide maintenance, or ``invalidate_mesh`` which purges both."""
    _CACHE.clear()
    _GRAPHS.clear()
    _PROGRAMS.clear()
    _PROVENANCE.clear()
    _CACHE_STATS.update(hits=0, misses=0, pipeline_s=0.0,
                        compiled_programs=0, l2_hits=0, l2_misses=0,
                        l2_quarantined=0, l2_writes=0, l2_fallbacks=0,
                        region_captures=0)


def invalidate_mesh(fingerprint: tuple) -> int:
    """Drop every cached program/graph compiled under ``fingerprint``.

    All in-memory caches' keys end with ``mesh_fingerprint()`` (it is the
    last component of ``_cfg_key``), so a mesh that left the job — a host
    evicted mid-serve — can be purged without touching programs compiled
    for other meshes.  Every attached on-disk L2 store is purged too (the
    sidecar records the fingerprint), so a dead mesh's programs cannot
    resurrect from disk in a later process.  Returns the number of evicted
    entries (memory + disk)."""
    n = 0
    for cache in (_CACHE, _GRAPHS, _PROGRAMS, _PROVENANCE):
        dead = [k for k in cache if k and k[-1] == fingerprint]
        for k in dead:
            del cache[k]
        n += len(dead)
    for l2 in _L2_INSTANCES.values():
        n += l2.invalidate(fingerprint)
    return n
