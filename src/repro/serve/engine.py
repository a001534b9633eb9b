"""Serving: slot-paged KV cache with mid-wave continuous batching.

``ServingEngine`` schedules requests over a fixed pool of ``slots`` — one
row of a paged per-layer KV cache ``[slots, max_len]`` plus a per-slot
length vector (``cache["pos"]``).  Occupancy is DATA, not shape:

* **admit** — a new request enters any free slot *mid-decode* via
  ``model.prefill_into_slot``: its prompt (right-padded to a power-of-two
  bucket) prefills in one shot and the K/V rows land at ``[slot, 0:plen]``
  through a dynamic-slot-start donated cache write.
* **decode** — every step runs ALL slots through
  ``model.decode_step_slots``: each block is ONE region program (per-slot
  RoPE rows gathered from the bucketed table, per-slot K/V scattered at
  ``(slot, pos[slot])`` via ``gather``/``scatter`` IR nodes, per-slot
  masked attention) replayed from the ``_PROGRAMS`` cache with one dict
  probe + one jit call, REGARDLESS of which slots are live.  Cache pages
  update in place (scatter donation) — zero per-step copies.
* **free** — a finished request releases its slot immediately; the next
  queued request takes it on the same scheduler tick.  No wave barrier:
  a straggler never blocks the rest of the batch.

``run_wave`` is the A/B baseline: the SAME slot primitives, but requests
admit in full batches and the batch decodes until its slowest member
finishes (the old wave semantics), with bitwise-identical per-request
outputs (per-slot compute never mixes rows across slots).

**Meshes.**  Slot scheduling composes with tensor parallelism: on a mesh
the engine runs the SAME slot loop — region programs capture under the
ambient mesh (the mesh fingerprint is part of every program key), the
``shard_act`` constraints inside the slot bodies are recorded as
``sharding`` annotations on region nodes and replayed as
``jax.lax.with_sharding_constraint`` at lowering, and the KV pages get
``[slots, max_len]`` NamedShardings from :func:`slot_cache_shardings`
(slots over the data axes, heads over ``model`` when divisible) so the
donated scatter writes stay in place per shard.  Per-request outputs are
bitwise-identical to the single-device slot engine.  Only families
without slot support (RWKV, Zamba2, enc-dec) still use the pjit'd
padded-wave loop (``make_prefill_step`` / ``make_decode_step``, KV
sequence dim sharded as "kvseq").

**State rows.**  A family that keeps recurrent state per request
(``model.slot_state_rows()``, granite's Mamba2 layers) holds one row of
each state array per slot beside its KV pages.  Admission resets the
slot's rows and the prefill writes the prompt's final state into them;
each decode step advances them in place; parking copies them to a park
row and resuming copies them back.  Such a model shares no prefix pages
(a prefix's KV without its state would be wrong), so its shared region
only parks preempted requests.

``ServeConfig.regions=False`` is the per-op control: the same slot loop
with every op dispatched eagerly.  Every ``run``/``run_wave`` call
populates ``ServingEngine.last_stats`` (tokens/sec, mean slot occupancy,
admitted/rejected/preempted counts, queue wait, time to first token and
inter-token gaps from each request's ``token_times``).

**Spans.**  Under a profiler session the slot loop writes host spans
(``repro.spans``): ``serve.tick`` per scheduler iteration, holding
``serve.admit`` (with its ``serve.pages.*`` operations and the blocking
``serve.prefill``; ``state_reset``: the bytes of state rows it reset),
the blocking ``serve.decode`` (``kv_pages``: the live KV pages the step
reads, summed over slots; ``state_rows``: the slots whose state rows it
advances), ``serve.release``,
``serve.preempt`` and ``serve.ckpt``.  The model's ``model.prefill`` /
``model.decode`` spans sit inside the two blocking ones and time the
host's dispatch alone.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.ckpt import restore_checkpoint, save_checkpoint
from repro.core.schedule import cost_model_for
from repro.core.tapir import (TapirConfig, cache_stats, invalidate_mesh,
                              use)
from repro.dist.fault import Fault, FaultInjector, StragglerWatchdog
from repro.dist.sharding import (batch_pspec, logical_to_pspec,
                                 param_shardings)
from repro.serve.pages import (PagePool, copy_cache_pages,
                               default_shared_pages, identity_row,
                               page_geometry, preempt_cost, private_page,
                               reset_state_rows, state_row_bytes)
from repro.spans import span


@dataclass(frozen=True)
class ServeConfig:
    mode: str = "tapir"
    strategy: str = "tp"
    max_len: int = 2048
    greedy: bool = True
    #: device kind whose cost model schedules the programs (a key of
    #: ``core.schedule.COST_MODELS``); None: the device this process uses
    target: Optional[str] = None
    # stateful region capture: each decode block (QKV, RoPE, KV-cache
    # writes, masked attention, MLP) traces into ONE TaskGraph and runs as
    # a single cached jit per step (cache donation applies at the outermost
    # jit — see module docstring).  False = per-op control (the
    # decode_region_vs_per_op A/B).
    regions: bool = True
    # admission policy: "strict" raises when a request's prompt + max_new
    # overflows the slot page (default — an overflow would silently drop
    # K/V rows and corrupt the output); "reject" marks it done=False,
    # counts it in ``last_stats["rejected"]`` and serves the rest of the
    # queue; "slo" additionally sheds requests whose ``deadline_s`` the
    # engine estimates it can no longer meet (observed step p50 x tokens
    # remaining), so a backed-up queue fails fast instead of late.
    admit_policy: str = "strict"
    # -- page policy (shared prefixes / preemption; see serve/pages.py) ---
    #: hash prompt prefixes at page granularity and bind resident shared
    #: pages on admit, prefilling only the divergent suffix
    prefix_sharing: bool = True
    #: KV page length (None: 64 when it divides max_len, else max_len);
    #: must divide max_len — see ``pages.page_geometry``
    page_len: Optional[int] = None
    #: shared-region size in pages (None: one slot's worth per slot)
    shared_pages: Optional[int] = None
    #: eviction arm for priority preemption: "auto" picks park vs replay
    #: by the ``preempt_cost`` roofline; "park"/"replay" force one arm
    preempt_mode: str = "auto"
    # -- fault tolerance (slot path; see ``_run_slots``) ------------------
    #: deterministic fault source, consulted before every pool decode step
    fault_injector: Optional[FaultInjector] = None
    #: slot-state checkpoints (KV pages, per-slot pos, queue, RNG) land
    #: here; None disables durability — recovery replays from scratch
    ckpt_dir: Optional[str] = None
    #: decode steps between periodic checkpoints (0 = on-demand only)
    ckpt_every: int = 0
    #: recoveries before the run gives up (persistent-failure backstop)
    max_failures: int = 8
    #: watchdog: a step slower than threshold x rolling median is flagged
    straggler_threshold: float = 4.0
    #: consecutive flagged steps before admission sheds load
    straggle_patience: int = 3
    #: shed pause starts at shed_base decode ticks and doubles per round
    #: (bounded exponential backoff) up to shed_cap
    shed_base: int = 2
    shed_cap: int = 16
    #: shed rounds with straggle persisting before the suspect host is
    #: evicted (checkpoint -> mesh shrink -> restore)
    straggle_escalate: int = 3
    # -- persistent program cache (L2; see ``repro.cache``) ---------------
    #: on-disk compiled-program store; None serves memory-only (every
    #: process pays its own XLA compiles)
    program_cache_dir: Optional[str] = None
    #: "off" | "read" (probe, never publish — replicas behind a shared
    #: read-only store) | "readwrite"
    cache_mode: str = "readwrite"

    def __post_init__(self):
        # fail at construction, not deep inside the decode loop
        if self.admit_policy not in ("strict", "reject", "slo"):
            raise ValueError(
                f"admit_policy must be 'strict', 'reject' or 'slo', "
                f"got {self.admit_policy!r}")
        if self.preempt_mode not in ("auto", "park", "replay"):
            raise ValueError(
                f"preempt_mode must be 'auto', 'park' or 'replay', "
                f"got {self.preempt_mode!r}")
        if self.shed_base < 0 or self.shed_cap < 0:
            raise ValueError(
                f"shed_base/shed_cap must be >= 0, got "
                f"{self.shed_base}/{self.shed_cap}")
        if self.page_len is not None and self.page_len <= 0:
            raise ValueError(f"page_len must be positive, got "
                             f"{self.page_len}")
        if self.shared_pages is not None and self.shared_pages < 0:
            raise ValueError(f"shared_pages must be >= 0, got "
                             f"{self.shared_pages}")

    def tapir_config(self) -> TapirConfig:
        if self.program_cache_dir and self.cache_mode == "readwrite":
            # before any eager dispatch of the run: the small-compile tier
            # (jax's own persistent cache) only helps ops compiled after it
            from repro.cache import enable_xla_disk_cache
            enable_xla_disk_cache()
        return TapirConfig(mode=self.mode,
                           cost_model=cost_model_for(self.target),
                           regions=self.regions,
                           program_cache_dir=self.program_cache_dir,
                           cache_mode=self.cache_mode)


def _shardings(specs, axes, mesh):
    """NamedSharding tree from parallel (ShapeDtypeStruct, logical-axes)
    trees — the single rule set for every serving cache layout."""
    def one(sds, ax):
        if not ax:
            return NamedSharding(mesh, P())
        spec = list(logical_to_pspec(ax, mesh, shape=sds.shape))
        # batch dim: shard over data axes like activations
        for i, a in enumerate(ax):
            if a == "batch":
                bp = batch_pspec(mesh, ndim=1, batch_size=sds.shape[i])
                spec[i] = bp[0]
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, specs, axes,
                                  is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def cache_shardings(model, mesh, batch: int, max_len: int):
    """NamedSharding tree for the model's padded-wave decode cache."""
    return _shardings(model.cache_specs(batch, max_len),
                      model.cache_axes(), mesh)


def slot_cache_shardings(model, mesh, slots: int, max_len: int,
                         page_len: Optional[int] = None,
                         shared_pages: Optional[int] = None):
    """NamedSharding tree for the slot-paged decode cache: per-layer
    ``[P, page_len, Hkv, hd]`` page pools with heads over ``model`` (when
    divisible); the page dims stay unsharded — per-slot scatters write at
    data-dependent pages, and sharding those dims would turn every decode
    write into a collective."""
    return _shardings(model.slot_cache_specs(slots, max_len, page_len,
                                             shared_pages),
                      model.slot_cache_axes(), mesh)


def slot_param_shardings(model, sp, mesh):
    """The decode TP layout of the ``slot_params`` tree ``sp`` (arrays or
    shape structs): a NamedSharding per leaf, kind markers passed through.

    Only a leaf's LAST dim is sharded, and only when its logical axis maps
    to ``model`` and divides: the GEMM *N* dims (wq/wk/wv/wg/wu/lm head —
    column sharding, every output element reduced locally) pin to the
    model axis, while *K*-dim-mapped weights (wo, wd: "heads"/"mlp" on the
    contraction dim) stay replicated — a K split would all-reduce partial
    sums and reorder float adds, breaking the bitwise serving invariant."""
    axes = model.slot_param_axes()

    def is_axes(x):
        return isinstance(x, tuple) and all(
            e is None or isinstance(e, str) for e in x)

    def one(ax, v):
        if not hasattr(v, "shape"):
            return v                     # ("dense"/"moe") kind markers
        last = (None,) * (len(ax) - 1) + (ax[-1],) if ax else ()
        spec = logical_to_pspec(last, mesh, shape=v.shape)
        spec = tuple(s if s == "model" else None for s in spec)
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, axes, sp, is_leaf=is_axes)


def pin_slot_params(model, sp, mesh):
    """``device_put`` the ``slot_params`` tree with its decode TP layout
    (``slot_param_shardings``) committed up front, instead of GSPMD
    re-deciding a layout per program."""
    shardings = slot_param_shardings(model, sp, mesh)
    return jax.tree_util.tree_map(
        lambda v, sh: jax.device_put(v, sh) if hasattr(v, "shape") else v,
        sp, shardings)


class _EngineFault(Exception):
    """Internal: aborts the slot session; carries the injected Fault."""

    def __init__(self, fault: Fault):
        super().__init__(f"injected fault: {fault}")
        self.fault = fault


@dataclass
class _SlotRunState:
    """Everything a slot session needs to resume: the device state
    (``cache`` pages + page table + ``rng``) checkpoints as one pytree —
    prefix pages live in the pool ONCE, never per-referencing-slot; the
    host-side scheduler and page-policy fields travel in the checkpoint's
    JSON ``meta``.  All of it rolls back together on restore, so replay
    is deterministic."""
    cache: Any
    rng: Any
    slot_idx: list               # per-slot index into ``requests``, -1 free
    slot_steps: list             # per-slot decode-step budget used
    tokens: np.ndarray           # [slots, 1] next feed token per slot
    pool: Any = None             # PagePool: shared-prefix / parking state
    ptab_host: Any = None        # np [slots, pps] mirror of cache["ptab"]
    pos_host: Any = None         # np [slots] mirror of cache["pos"]
    pending: list = field(default_factory=list)  # indices awaiting a slot
    fed: list = field(default_factory=list)      # per-slot out tokens fed
    slot_seq: list = field(default_factory=list)  # admission order stamp
    seq: int = 0                 # admission sequence counter
    parked: dict = field(default_factory=dict)   # rid -> feed-state record
    step: int = 0                # completed pool-wide decode steps
    occ_sum: float = 0.0
    st: dict = field(default_factory=dict)
    backoff: int = 0             # admission pause ticks remaining (shed)
    shed_rounds: int = 0
    straggle_run: int = 0        # consecutive flagged steps
    suspect: Optional[int] = None  # device id blamed for the straggle


def make_prefill_step(model, mesh, cfg: ServeConfig = ServeConfig()):
    tap = cfg.tapir_config()
    p_sh = param_shardings(model.param_axes(), model.param_sds(), mesh,
                           strategy=cfg.strategy)

    def prefill(params, tokens, cache):
        with use(tap):
            return model.prefill(params, tokens, cache)

    return jax.jit(prefill, in_shardings=(p_sh, None, None),
                   donate_argnums=(2,)), p_sh


def make_decode_step(model, mesh, cfg: ServeConfig = ServeConfig()):
    """decode(params, tokens [B,1], cache) -> (next_token [B], cache)."""
    tap = cfg.tapir_config()
    p_sh = param_shardings(model.param_axes(), model.param_sds(), mesh,
                           strategy=cfg.strategy)

    def decode(params, tokens, cache):
        with use(tap):
            logits, cache = model.decode_step(params, tokens, cache)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, cache

    return jax.jit(decode, in_shardings=(p_sh, None, None),
                   donate_argnums=(2,)), p_sh


@dataclass
class Request:
    rid: int
    prompt: np.ndarray           # [S] int32
    max_new: int = 32
    #: scheduling priority, 0 (lowest) .. 9 (highest).  A waiting
    #: higher-priority request may preempt a running lower-priority slot.
    priority: int = 0
    #: SLO deadline in seconds from run start (admit_policy="slo" sheds
    #: requests the engine estimates it can no longer finish in time)
    deadline_s: Optional[float] = None
    #: earliest pool decode step at which the request becomes
    #: schedulable (0 = available immediately) — lets tests and traces
    #: model staggered arrivals deterministically
    arrival_step: int = 0
    out: list = field(default_factory=list)
    done: bool = False
    #: host clock (``time.perf_counter``) at which each token of ``out``
    #: was read back; one reading per scheduler tick, shared by its tokens
    token_times: list = field(default_factory=list)

    def __post_init__(self):
        if not 0 <= int(self.priority) <= 9:
            raise ValueError(
                f"request {self.rid}: priority must be in 0..9, got "
                f"{self.priority}")
        if self.arrival_step < 0:
            raise ValueError(
                f"request {self.rid}: arrival_step must be >= 0, got "
                f"{self.arrival_step}")


class ServingEngine:
    """Host-side serving loop: a slot allocator over a paged KV cache
    (continuous batching, greedy sampling) — see the module docstring."""

    def __init__(self, model, params, mesh=None, batch: int = 8,
                 max_len: int = 2048, cfg: ServeConfig = ServeConfig()):
        self.model, self.params = model, params
        self.batch, self.max_len = batch, max_len
        self.slots = batch
        self.cfg = cfg
        self.mesh = mesh
        #: scheduling stats of the most recent ``run``/``run_wave`` call
        self.last_stats: dict = {}
        #: pre-sliced slot params, built on the first slot run; from then
        #: on they are the engine's only weights (``params`` is dropped)
        self._sp = None
        #: model FLOPs per token (2 x params), for the preemption roofline
        self._flops_tok = 2.0 * sum(
            int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(params)
            if hasattr(v, "shape"))
        # slot scheduling runs wherever the family implements the slot
        # API — including TP meshes, where the slot regions capture under
        # the ambient mesh and replay their sharding constraints at
        # lowering.  Only families without slot support (RWKV, Zamba2,
        # enc-dec) use the pjit'd padded-wave loop.
        self._slot_capable = getattr(model, "supports_slots",
                                     lambda: False)()
        # per-slot recurrent state beside the KV pages: a prefix's pages
        # without its state would be wrong, so such a model shares no
        # prefix, and its shared region only parks preempted requests
        self._state = bool(self._slot_capable and model.slot_state_rows())
        self._prefix_sharing = cfg.prefix_sharing and not self._state
        self._shared_pages = cfg.shared_pages
        if self._shared_pages is None and self._state:
            self._shared_pages = default_shared_pages(
                batch, page_geometry(max_len, cfg.page_len)[1], True)
        # the pjit'd padded-wave steps are only reachable for slot-less
        # families, so they build lazily on first use — a dense/MoE engine
        # (mesh or not) never pays for them
        self._prefill: Optional[Callable] = None
        self._decode: Optional[Callable] = None

    def _ensure_padded_steps(self) -> None:
        if self._prefill is not None:
            return
        model, cfg = self.model, self.cfg
        if self.mesh is not None:
            self._prefill = make_prefill_step(model, self.mesh, cfg)[0]
            self._decode = make_decode_step(model, self.mesh, cfg)[0]
            return
        tap = cfg.tapir_config()

        def _pf(params, tokens, cache):
            with use(tap):
                return model.prefill(params, tokens, cache)

        def _dc(params, tokens, cache):
            with use(tap):
                logits, cache = model.decode_step(params, tokens, cache)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        # donate the cache like the mesh path does: the outer jit owns
        # the in-place update (the region's inner donation inlines away
        # under an enclosing jit)
        self._prefill = jax.jit(_pf, donate_argnums=(2,))
        self._decode = jax.jit(_dc, donate_argnums=(2,))

    # -- scheduling -------------------------------------------------------
    def run(self, requests: list[Request],
            max_steps: int = 256) -> list[Request]:
        """Continuous batching: requests admit into free slots mid-decode,
        finished slots free immediately.  ``max_steps`` caps each
        request's decode-step budget (a request that exhausts it frees
        its slot with ``done=False``), matching the wave loop's per-wave
        cap."""
        if not self._slot_capable:
            return self._run_padded_waves(requests, max_steps)
        return self._run_slots(requests, max_steps, continuous=True)

    def run_wave(self, requests: list[Request],
                 max_steps: int = 256) -> list[Request]:
        """A/B baseline: the same slot primitives with WAVE scheduling —
        admit a full batch, decode until every member finishes, repeat.
        Slots that finish early idle until the wave's slowest request
        drains (the utilization gap the continuous scheduler removes)."""
        if not self._slot_capable:
            return self._run_padded_waves(requests, max_steps)
        return self._run_slots(requests, max_steps, continuous=False)

    def _mesh_ctx(self):
        """Ambient-mesh context for the slot loop: region programs capture
        (and key) under it, so sharding constraints resolve and replay."""
        return jax.set_mesh(self.mesh) if self.mesh is not None \
            else nullcontext()

    def _init_slot_cache(self):
        """Fresh slot cache; on a multi-device mesh the pages are placed
        with their NamedShardings up front so the donated scatter writes
        alias in place per shard (an unsharded page would reshard on the
        first constrained write and break the donation)."""
        cfg = self.cfg
        cache = self.model.init_slot_cache(self.slots, self.max_len,
                                           cfg.page_len, self._shared_pages)
        if self.mesh is not None and getattr(self.mesh, "size", 1) > 1:
            sh = slot_cache_shardings(self.model, self.mesh, self.slots,
                                      self.max_len, cfg.page_len,
                                      self._shared_pages)
            cache = jax.tree_util.tree_map(jax.device_put, cache, sh)
        return cache

    # -- fault-tolerant slot loop -----------------------------------------
    def _mesh_fp(self) -> tuple:
        """Structural fingerprint of ``self.mesh`` (same shape as
        ``passes.mesh_fingerprint()``, but of an explicit mesh)."""
        m = self.mesh
        if m is None:
            return ()
        shape = m.shape
        return tuple((a, int(shape[a])) for a in m.axis_names)

    def _build_slot_params(self):
        """Slice the stacked params into per-layer slot leaves and drop the
        engine's reference to the stacked tree, so one copy of the weights
        stays resident (a caller that keeps no reference of its own frees
        it here)."""
        sp = self.model.slot_params(self.params)
        self.params = None
        if self.mesh is not None and getattr(self.mesh, "size", 1) > 1:
            sp = pin_slot_params(self.model, sp, self.mesh)
        return sp

    def _slot_state_template(self):
        """ShapeDtypeStruct pytree of the checkpointable device state."""
        return {"cache": self.model.slot_cache_specs(
                    self.slots, self.max_len, self.cfg.page_len,
                    self._shared_pages),
                "rng": jax.ShapeDtypeStruct((2,), jnp.uint32)}

    def _slot_state_shardings(self):
        if self.mesh is None or getattr(self.mesh, "size", 1) <= 1:
            return None
        return {"cache": slot_cache_shardings(self.model, self.mesh,
                                              self.slots, self.max_len,
                                              self.cfg.page_len,
                                              self._shared_pages),
                "rng": NamedSharding(self.mesh, P())}

    def _fresh_slot_state(self, requests) -> _SlotRunState:
        for r in requests:
            r.out, r.done, r.token_times = [], False, []
        pool = PagePool(self.slots, self.max_len, self.cfg.page_len,
                        self._shared_pages, self._state)
        return _SlotRunState(
            cache=self._init_slot_cache(),
            # greedy today; checkpointed so a sampler slots into the same
            # recovery protocol without changing the state schema
            rng=jax.random.PRNGKey(0),
            slot_idx=[-1] * self.slots,
            slot_steps=[0] * self.slots,
            tokens=np.zeros((self.slots, 1), np.int32),
            pool=pool,
            ptab_host=np.stack([identity_row(s, pool.pps)
                                for s in range(self.slots)]),
            pos_host=np.zeros(self.slots, np.int64),
            pending=list(range(len(requests))),
            fed=[0] * self.slots,
            slot_seq=[0] * self.slots,
            st={"tokens": 0, "admitted": 0, "rejected": 0, "preempted": 0,
                "decode_steps": 0, "prefix_hits": 0,
                "prefix_tokens_saved": 0, "preemptions": 0, "parked": 0,
                "replayed": 0, "slo_shed": 0})

    def _save_slot_ckpt(self, rs: _SlotRunState, requests, ft: dict) -> None:
        """One atomic snapshot: KV pages + per-slot pos + RNG as the device
        pytree; queue cursor, slot assignments, feed tokens, every
        admitted request's progress and the rolled-back stats as JSON
        meta.  Restore rewinds ALL of it together, so replay from the
        checkpoint is deterministic."""
        if self.cfg.ckpt_dir is None:
            return
        with span("serve.ckpt", step=rs.step):
            meta = {"step": rs.step,
                    "pending": [int(i) for i in rs.pending],
                    "slot_idx": [int(i) for i in rs.slot_idx],
                    "slot_steps": [int(s) for s in rs.slot_steps],
                    "tokens": [int(t) for t in rs.tokens[:, 0]],
                    "fed": [int(f) for f in rs.fed],
                    "slot_seq": [int(q) for q in rs.slot_seq],
                    "seq": int(rs.seq),
                    "outs": {str(i): [int(t) for t in requests[i].out]
                             for i in range(len(requests)) if requests[i].out},
                    "done": [i for i, r in enumerate(requests) if r.done],
                    "parked": {str(r): {"tok": int(v["tok"]),
                                        "steps": int(v["steps"]),
                                        "fed": int(v["fed"])}
                               for r, v in rs.parked.items()},
                    "pool": rs.pool.to_meta(),
                    "st": {k: int(v) for k, v in rs.st.items()},
                    "occ_sum": float(rs.occ_sum)}
            save_checkpoint(self.cfg.ckpt_dir, rs.step,
                            {"cache": rs.cache, "rng": rs.rng},
                            keep_n=2, blocking=True, meta=meta)
        ft["checkpoints"] += 1

    def _restore_slot_state(self, requests, ft: dict) -> _SlotRunState:
        """Latest slot checkpoint -> run state, loaded through the elastic
        ``shardings=`` path (the CURRENT mesh's shardings — after a shrink
        this is the reshard-on-load).  No checkpoint: full reset; greedy
        decode is deterministic, so replay from scratch still converges to
        the clean run's outputs."""
        ft["restores"] += 1
        if self.cfg.ckpt_dir is not None:
            try:
                state, _, manifest = restore_checkpoint(
                    self.cfg.ckpt_dir, self._slot_state_template(),
                    shardings=self._slot_state_shardings())
            except FileNotFoundError:
                return self._fresh_slot_state(requests)
            if self._slot_state_shardings() is None:
                state = jax.tree_util.tree_map(jnp.asarray, state)
            meta = manifest["meta"]
            done = set(meta["done"])
            for i, r in enumerate(requests):
                out = meta["outs"].get(str(i))
                r.out = list(out) if out is not None else []
                r.token_times = r.token_times[:len(r.out)]
                r.done = i in done
            return _SlotRunState(
                cache=state["cache"], rng=state["rng"],
                slot_idx=list(meta["slot_idx"]),
                slot_steps=list(meta["slot_steps"]),
                tokens=np.asarray(meta["tokens"], np.int32).reshape(-1, 1),
                pool=PagePool.from_meta(meta["pool"], self.slots,
                                        self.max_len, self.cfg.page_len,
                                        self._shared_pages, self._state),
                ptab_host=np.array(state["cache"]["ptab"]),
                pos_host=np.array(state["cache"]["pos"], np.int64),
                pending=list(meta["pending"]),
                fed=list(meta["fed"]),
                slot_seq=list(meta["slot_seq"]), seq=int(meta["seq"]),
                parked={int(r): dict(v)
                        for r, v in meta["parked"].items()},
                step=int(meta["step"]),
                occ_sum=float(meta["occ_sum"]), st=dict(meta["st"]))
        return self._fresh_slot_state(requests)

    def _handle_fault(self, fault: Fault, ft: dict) -> None:
        """Post-mortem reconfiguration: a fault blaming a mesh host evicts
        it (shrunk mesh -> new shardings -> ``_cfg_key`` miss -> clean
        recompile); the dead fingerprint's programs are purged so nothing
        stale can replay.  A crash without a blamed host restores on the
        same mesh — programs and pinned params survive, so replay is a
        cache hit."""
        old_fp = self._mesh_fp()
        if fault.host is not None and self.mesh is not None:
            from repro.launch.mesh import shrink_mesh
            try:
                new_mesh = shrink_mesh(self.mesh, fault.host)
            except ValueError:
                new_mesh = None     # not in mesh / pure TP: same-mesh retry
            if new_mesh is not None:
                self.mesh = new_mesh
                ft["mesh_shrinks"] += 1
        if self._mesh_fp() != old_fp:
            invalidate_mesh(old_fp)
            if self._sp is not None:    # re-pin params on the new mesh
                self._sp = pin_slot_params(self.model, self._sp, self.mesh)

    def _run_slots(self, requests, max_steps: int, continuous: bool):
        """Recovery loop around the slot session: a session runs until an
        injected (or escalated) fault aborts it; the handler reconfigures
        the mesh, the next attempt restores the latest checkpoint and
        replays.  Per-request outputs stay bitwise identical to a no-fault
        run — everything the session consumes (pages, pos, queue, feed
        tokens, request progress) rolls back to one consistent snapshot
        and greedy decode is deterministic."""
        cfg = self.cfg
        wd = StragglerWatchdog(threshold=cfg.straggler_threshold)
        ft = {"failures": 0, "restores": 0, "mesh_shrinks": 0,
              "checkpoints": 0, "shed_steps": 0, "shed_rounds": 0}
        self._cache_snap = self._snap_cache()
        t0 = time.perf_counter()
        # wall-clock observability rides OUTSIDE the checkpointed stats
        # ("_"-keys are stripped before they reach ``last_stats``)
        ft["_t0"] = t0
        ft["_ttft"] = []
        ft["_qwait"] = []
        resume = False
        while True:
            try:
                with self._mesh_ctx(), use(cfg.tapir_config()):
                    if self._sp is None:
                        self._sp = self._build_slot_params()
                    rs = self._restore_slot_state(requests, ft) if resume \
                        else self._fresh_slot_state(requests)
                    self._slot_session(requests, max_steps, continuous,
                                       rs, ft, wd)
                break
            except _EngineFault as ef:
                ft["failures"] += 1
                if ft["failures"] > cfg.max_failures:
                    raise RuntimeError(
                        f"slot serving failed {ft['failures']} times; "
                        "giving up") from ef
                self._handle_fault(ef.fault, ft)
                resume = True
        st = rs.st
        ttft = ft.pop("_ttft")
        qwait = ft.pop("_qwait")
        ft.pop("_t0")

        def pct(xs, q):
            return float(np.percentile(xs, q)) if xs else 0.0

        itl = [b - a for r in requests
               for a, b in zip(r.token_times, r.token_times[1:])]
        st.update(ft, straggler_steps=len(wd.flagged),
                  step_p50=wd.p50, step_p95=wd.p95,
                  ttft_p50=pct(ttft, 50), ttft_p95=pct(ttft, 95),
                  queue_wait_p50=pct(qwait, 50),
                  queue_wait_p95=pct(qwait, 95),
                  itl_p50=pct(itl, 50), itl_p95=pct(itl, 95))
        self._set_stats(st, rs.occ_sum, time.perf_counter() - t0)
        return requests

    # -- page-policy helpers ---------------------------------------------
    def _push_ptab(self, rs: _SlotRunState) -> None:
        """Mirror the host page table to the device: page indirection is
        DATA, so this is the only thing a rebinding ever changes."""
        t = jnp.asarray(rs.ptab_host)
        if self.mesh is not None and getattr(self.mesh, "size", 1) > 1:
            t = jax.device_put(t, NamedSharding(self.mesh, P()))
        rs.cache["ptab"] = t

    def _release(self, s: int, rs: _SlotRunState, slot_req) -> None:
        """Free slot ``s``: drop its shared-prefix binding and reset its
        page-table row to the private identity run."""
        slot_req[s] = None
        rs.slot_idx[s] = -1
        self._release_fresh(s, rs)

    def _page_bytes(self, rs: _SlotRunState) -> int:
        """Bytes one page copy moves (K+V, all layers)."""
        k0 = rs.cache["k"][0]
        per = int(np.prod(k0.shape[1:])) * k0.dtype.itemsize
        return per * len(rs.cache["k"]) * 2

    def _admit_into(self, requests, idx: int, s: int, rs: _SlotRunState,
                    slot_req, ft: dict) -> None:
        """Admit ``requests[idx]`` into free slot ``s``: resume it from
        parked pages, replay it from its recorded tokens, or prefill it
        fresh — binding any resident shared prefix first so only the
        divergent suffix runs."""
        from repro.models.layers import bucket_pow2
        model, cfg, pool, sp = self.model, self.cfg, rs.pool, self._sp
        r = requests[idx]
        plen = len(r.prompt)
        with span("serve.admit", rid=r.rid, plen=plen) as adm:
            # the slot page run must hold every position a decode step will
            # write: rows [0, plen + max_new - 1).  Past capacity the scatter
            # would DROP new K/V rows while sampling continued — corrupt
            # output, so reject at admission instead.
            if plen + r.max_new - 1 > self.max_len:
                if cfg.admit_policy in ("reject", "slo"):
                    rs.pending.remove(idx)
                    rs.st["rejected"] += 1
                    return
                raise ValueError(
                    f"request {r.rid}: prompt ({plen}) + "
                    f"max_new ({r.max_new}) overflows the "
                    f"slot page (max_len={self.max_len})")
            rs.pending.remove(idx)
            if r.rid in pool.parked:
                # resume: pages copied back bitwise, feed state restored —
                # the continuation is indistinguishable from never evicting
                rec = pool.resume(rs.cache, r.rid, s)
                row = identity_row(s, pool.pps)
                ent = pool.entries.get(rec["entry"]) if rec["entry"] else None
                if ent is not None:
                    row[:rec["bound"]] = ent.pages[:rec["bound"]]
                rs.ptab_host[s] = row
                self._push_ptab(rs)
                rs.cache["pos"] = rs.cache["pos"].at[s].set(rec["length"])
                rs.pos_host[s] = rec["length"]
                hp = rs.parked.pop(r.rid)
                rs.tokens[s, 0] = hp["tok"]
                rs.slot_steps[s] = hp["steps"]
                rs.fed[s] = hp["fed"]
                slot_req[s] = r
                rs.slot_idx[s] = idx
                rs.seq += 1
                rs.slot_seq[s] = rs.seq
                return
            replaying = bool(r.out)
            if not replaying:
                ft["_qwait"].append(time.perf_counter() - ft["_t0"])
            prompt = np.asarray(r.prompt, np.int32)
            k, pages = pool.lookup(prompt) if self._prefix_sharing \
                else (0, [])
            row = identity_row(s, pool.pps)
            start = 0
            if k > 0:
                pool.bind(s, prompt, k)
                if plen == k * pool.page_len:
                    # exact cover: the prompt's last token must re-run for
                    # its logits, and its K/V write would scatter into the
                    # boundary shared page — COW it into the private run
                    copy_cache_pages(rs.cache, [pages[k - 1]],
                                     [private_page(s, k - 1, pool.pps)])
                    pool.slot_bound[s] = k - 1
                    row[:k - 1] = pages[:k - 1]
                    start = plen - 1
                else:
                    row[:k] = pages[:k]
                    start = k * pool.page_len
                rs.st["prefix_hits"] += 1
                rs.st["prefix_tokens_saved"] += start
            rs.ptab_host[s] = row
            self._push_ptab(rs)
            suf = prompt[start:]
            padded = np.zeros((1, min(bucket_pow2(len(suf)), self.max_len)),
                              np.int32)
            padded[0, :len(suf)] = suf
            # a fresh request starts from zero state: its rows are reset
            # before its prefill writes the prompt's final state into them
            reset_state_rows(rs.cache, s)
            adm.set_metadata(bucket=padded.shape[1], prefix_pages=k,
                             state_reset=state_row_bytes(rs.cache))
            with span("serve.prefill", rid=r.rid, tokens=len(suf)):
                logits, rs.cache = model.prefill_into_slot(
                    sp, jnp.asarray(padded), rs.cache, s, plen, start=start)
                tok = int(np.asarray(jnp.argmax(logits, -1))[0])
            rs.pos_host[s] = plen
            if not replaying:
                now = time.perf_counter()
                r.out.append(tok)
                r.token_times.append(now)
                rs.st["admitted"] += 1
                rs.st["tokens"] += 1
                ft["_ttft"].append(now - ft["_t0"])
            if self._prefix_sharing and k == 0:
                # total miss: publish the prompt-covering pages so the NEXT
                # request sharing this prefix prefills only its suffix
                pool.publish(rs.cache, s, prompt)
            rs.fed[s] = 1
            rs.tokens[s, 0] = r.out[0]
            if not replaying and len(r.out) >= r.max_new:
                r.done = True
                self._release_fresh(s, rs)
                return
            slot_req[s] = r
            rs.slot_idx[s] = idx
            # a replayed request already spent the steps that produced its
            # recorded tokens; the budget continues, it does not reset
            rs.slot_steps[s] = len(r.out) - 1 if replaying else 0
            rs.seq += 1
            rs.slot_seq[s] = rs.seq

    def _release_fresh(self, s: int, rs: _SlotRunState) -> None:
        """Unbind slot ``s``'s shared prefix and reset its page-table row
        and length (a slot that finished at prefill holds no request)."""
        with span("serve.release", slot=s):
            rs.pool.unbind(s)
            rs.ptab_host[s] = identity_row(s, rs.pool.pps)
            self._push_ptab(rs)
            rs.cache["pos"] = rs.cache["pos"].at[s].set(0)
            rs.pos_host[s] = 0

    def _slo_shed(self, requests, elig: list, rs: _SlotRunState,
                  ft: dict, wd) -> list:
        """admit_policy="slo": drop eligible requests whose deadline the
        engine estimates it can no longer meet (remaining tokens at the
        observed p50 step time), so they fail fast instead of late."""
        if self.cfg.admit_policy != "slo":
            return elig
        now = time.perf_counter() - ft["_t0"]
        keep = []
        for i in elig:
            r = requests[i]
            if r.deadline_s is not None:
                est = (r.max_new - len(r.out)) * (wd.p50 or 0.0)
                if now + est > r.deadline_s:
                    rs.pending.remove(i)
                    rs.st["rejected"] += 1
                    rs.st["slo_shed"] += 1
                    continue
            keep.append(i)
        return keep

    def _preempt_for(self, requests, idx: int, rs: _SlotRunState,
                     slot_req, ft: dict, wd) -> Optional[int]:
        """Priority preemption: evict the lowest-priority running slot
        (ties: most recently admitted) iff ``requests[idx]`` outranks it
        STRICTLY.  The victim is parked (pages copied into the shared
        region) or dropped for replay-from-prefix — whichever the
        ``preempt_cost`` roofline prices cheaper — and re-enters the
        pending queue.  Returns the freed slot, or None."""
        cfg, pool = self.cfg, rs.pool
        occ = [(requests[rs.slot_idx[s]].priority, -rs.slot_seq[s], s)
               for s in range(self.slots) if slot_req[s] is not None]
        if not occ:
            return None
        vprio, _, s = min(occ)
        if requests[idx].priority <= vprio:
            return None
        victim = slot_req[s]
        with span("serve.preempt", rid=victim.rid):
            length = int(rs.pos_host[s])
            arm = cfg.preempt_mode
            if arm == "auto":
                arm = preempt_cost(
                    cost_model_for(cfg.target), length=length,
                    prefix_len=pool.slot_bound[s] * pool.page_len,
                    n_out=len(victim.out), page_bytes=self._page_bytes(rs),
                    pps=pool.pps, page_len=pool.page_len,
                    model_flops_per_tok=self._flops_tok,
                    step_s=(wd.p50 or 1e-3),
                    row_bytes=state_row_bytes(rs.cache)).arm
            if arm == "park":
                if pool.park(rs.cache, victim.rid, s, length):
                    rs.parked[victim.rid] = {"tok": int(rs.tokens[s, 0]),
                                             "steps": rs.slot_steps[s],
                                             "fed": rs.fed[s]}
                    rs.st["parked"] += 1
                else:
                    arm = "replay"     # shared region full: drop the pages
            if arm == "replay":
                pool.unbind(s)
                rs.st["replayed"] += 1
            rs.st["preemptions"] += 1
            rs.pending.append(rs.slot_idx[s])
            slot_req[s] = None
            rs.slot_idx[s] = -1
            rs.ptab_host[s] = identity_row(s, pool.pps)
            self._push_ptab(rs)
            rs.cache["pos"] = rs.cache["pos"].at[s].set(0)
            rs.pos_host[s] = 0
        return s

    def _slot_session(self, requests, max_steps: int, continuous: bool,
                      rs: _SlotRunState, ft: dict,
                      wd: StragglerWatchdog) -> None:
        model, cfg = self.model, self.cfg
        sp = self._sp
        injector = cfg.fault_injector

        def eligible():
            # highest priority first; FIFO (submission index) within one
            return sorted((i for i in rs.pending
                           if requests[i].arrival_step <= rs.step),
                          key=lambda i: (-requests[i].priority, i))

        slot_req: list[Optional[Request]] = [
            requests[i] if i >= 0 else None for i in rs.slot_idx]
        while rs.pending or any(r is not None for r in slot_req):
            with span("serve.tick", step=rs.step,
                      live=self.slots - slot_req.count(None)):
                if rs.backoff > 0:
                    # shedding: admission paused, existing slots keep draining
                    rs.backoff -= 1
                    ft["shed_steps"] += 1
                # -- admission: continuous fills ANY free slot on every
                # tick; wave only refills once the whole pool drained
                elif continuous or all(r is None for r in slot_req):
                    elig = self._slo_shed(requests, eligible(), rs, ft, wd)
                    for idx in elig:
                        s = next((t for t in range(self.slots)
                                  if slot_req[t] is None), None)
                        if s is None:
                            break
                        self._admit_into(requests, idx, s, rs, slot_req, ft)
                    if continuous:
                        # no free slot left: a strictly higher-priority
                        # arrival may evict one running victim per tick
                        elig = eligible()
                        if elig and all(r is not None for r in slot_req):
                            s = self._preempt_for(requests, elig[0], rs,
                                                  slot_req, ft, wd)
                            if s is not None:
                                self._admit_into(requests, elig[0], s, rs,
                                                 slot_req, ft)
                if not any(r is not None for r in slot_req):
                    if rs.pending:
                        # nothing runnable yet (future arrival_step): advance
                        # the scheduler clock without a decode step
                        rs.step += 1
                    continue
                # -- injected faults for the upcoming pool step: hard faults
                # abort the session (the recovery loop restores); straggle
                # slows THIS step so the watchdog sees it like a real one
                delay = 0.0
                if injector is not None:
                    f = injector.on_decode_step(rs.step)
                    if f is not None and f.kind in ("host", "crash"):
                        raise _EngineFault(f)
                    if f is not None and f.kind == "straggle":
                        delay = f.delay_s
                        if f.host is not None:
                            rs.suspect = f.host
                # -- one decode step for the WHOLE pool (free slots carry
                # don't-care tokens; their writes drop / get overwritten)
                rs.occ_sum += sum(r is not None for r in slot_req) / self.slots
                rs.st["decode_steps"] += 1
                t_step = time.perf_counter()
                if delay:
                    time.sleep(delay)
                # pages the paged decode kernel walks: every slot's live
                # ones, from the host's mirror of the lengths
                live = np.clip(rs.pos_host + 1, 1, self.max_len)
                kv_pages = int(np.sum(-(-live // rs.pool.page_len)))
                # state rows the step advances: every slot holding a request
                state_rows = int(np.count_nonzero(rs.pos_host)) \
                    if self._state else 0
                with span("serve.decode", step=rs.step, kv_pages=kv_pages,
                          state_rows=state_rows):
                    logits, rs.cache = model.decode_step_slots(
                        sp, jnp.asarray(rs.tokens), rs.cache)
                    nxt = np.asarray(
                        jnp.argmax(logits, -1).astype(jnp.int32))
                rs.pos_host += 1
                t_tok = time.perf_counter()
                dt = t_tok - t_step
                for s, r in enumerate(slot_req):
                    if r is None:
                        continue
                    tok = int(nxt[s])
                    if rs.fed[s] < len(r.out):
                        # replaying a preempted request: this token is
                        # already recorded — feed the record forward, count
                        # nothing (greedy decode re-derives the same token)
                        rs.tokens[s, 0] = r.out[rs.fed[s]]
                        rs.fed[s] += 1
                        continue
                    r.out.append(tok)
                    r.token_times.append(t_tok)
                    rs.fed[s] += 1
                    rs.st["tokens"] += 1
                    rs.tokens[s, 0] = tok
                    rs.slot_steps[s] += 1
                    if len(r.out) >= r.max_new:
                        r.done = True
                    if r.done or rs.slot_steps[s] >= max_steps:
                        if not r.done:
                            rs.st["preempted"] += 1
                        self._release(s, rs, slot_req)  # budget/done: free
                rs.step += 1
                # -- straggler policy: sustained straggle sheds admission with
                # bounded exponential backoff; persisting past the budget, it
                # escalates to evicting the suspect host (checkpoint first)
                if wd.observe(rs.step - 1, dt):
                    rs.straggle_run += 1
                else:
                    rs.straggle_run = 0
                if (rs.straggle_run >= cfg.straggle_patience
                        and rs.backoff == 0):
                    if rs.shed_rounds >= cfg.straggle_escalate:
                        self._save_slot_ckpt(rs, requests, ft)
                        raise _EngineFault(Fault("host", host=rs.suspect))
                    rs.shed_rounds += 1
                    ft["shed_rounds"] += 1
                    rs.backoff = min(cfg.shed_cap,
                                     cfg.shed_base * 2 ** (rs.shed_rounds - 1))
                    rs.straggle_run = 0
                    self._save_slot_ckpt(rs, requests, ft)     # on-demand
                elif cfg.ckpt_every > 0 and rs.step % cfg.ckpt_every == 0:
                    self._save_slot_ckpt(rs, requests, ft)

    #: cache counters surfaced per run as deltas in ``last_stats`` — a
    #: warm replica shows ``compiled_programs=0, l2_hits>0``
    _CACHE_KEYS = ("compiled_programs", "region_captures", "l2_hits",
                   "l2_misses", "l2_quarantined", "l2_writes", "l2_fallbacks")

    def _snap_cache(self) -> dict:
        s = cache_stats()
        return {k: s[k] for k in self._CACHE_KEYS}

    def _set_stats(self, st: dict, occ_sum: float, wall_s: float) -> None:
        st["wall_s"] = wall_s
        st["tok_per_s"] = st["tokens"] / wall_s if wall_s > 0 else 0.0
        st["mean_occupancy"] = (occ_sum / st["decode_steps"]
                                if st["decode_steps"] else 0.0)
        snap = getattr(self, "_cache_snap", None)
        if snap is not None:
            now = self._snap_cache()
            st.update({k: now[k] - snap[k] for k in self._CACHE_KEYS})
        self.last_stats = st

    # -- legacy padded-wave loop (mesh path / families without slots) -----
    def _run_padded_waves(self, requests: list[Request],
                          max_steps: int = 256) -> list[Request]:
        """Padded-batch waves over ``model.prefill``/``decode_step``
        (prompts left-PADDED to one shared length, i.e. right-aligned —
        pad tokens sit at the sequence start and get attended; the wave
        blocks until its slowest member finishes)."""
        self._ensure_padded_steps()
        st = {"tokens": 0, "admitted": 0, "rejected": 0, "preempted": 0,
              "decode_steps": 0}
        occ_sum = 0.0
        self._cache_snap = self._snap_cache()
        t0 = time.perf_counter()
        for wave_start in range(0, len(requests), self.batch):
            wave = requests[wave_start: wave_start + self.batch]
            B = len(wave)
            st["admitted"] += B
            S = max(len(r.prompt) for r in wave)
            toks = np.zeros((B, S), np.int32)
            for i, r in enumerate(wave):
                toks[i, S - len(r.prompt):] = r.prompt  # left-pad
            cache = self.model.init_cache(B, self.max_len)
            logits, cache = self._prefill(self.params, jnp.asarray(toks), cache)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32) if logits.ndim > 1 \
                else logits
            steps = 0
            while not all(r.done for r in wave) and steps < max_steps:
                occ_sum += sum(not r.done for r in wave) / self.batch
                st["decode_steps"] += 1
                nxt_np = np.asarray(nxt)
                for i, r in enumerate(wave):
                    if not r.done:
                        r.out.append(int(nxt_np[i]))
                        st["tokens"] += 1
                        if len(r.out) >= r.max_new:
                            r.done = True
                nxt, cache = self._decode(self.params, nxt[:, None]
                                          if nxt.ndim == 1 else nxt, cache)
                if nxt.ndim > 1:
                    nxt = nxt[:, 0]
                steps += 1
            st["preempted"] += sum(not r.done for r in wave)
        self._set_stats(st, occ_sum, time.perf_counter() - t0)
        return requests
