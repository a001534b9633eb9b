"""Late scheduling: bind fork-join parallelism to hardware AFTER optimization.

TapirXLA's central design point is that XLA's high-level code generator makes
task-partitioning decisions *before* the optimizer has run, using per-op
heuristics, while Tapir/LLVM schedules *after* optimization using a cost
model over the optimized code.  This module is the TPU analogue:

* ``CostModel`` carries the target-hardware constants (MXU shape, VMEM size,
  HBM bandwidth, grain-size threshold — the moral equivalent of Cilk's
  spawn overhead).
* ``assign_schedules`` walks the *fused* graph and binds each parallel dim to
  ``mesh:<axis>`` / ``grid`` / ``serial`` / ``vector``, picks MXU-aligned tile
  sizes that fit VMEM (strip-mining), serializes small tasks, and binds each
  library node's IMPLEMENTATION: every library op (matmul, attention,
  paged_attention, linear_scan, ssm_state_step, conv2d) has a registry of
  candidate lowerings (``IMPL_REGISTRY``), each carrying a roofline cost
  estimate (FLOPs + bytes moved + serial dispatch steps, per shard) and
  availability constraints; the argmin is bound to ``node.schedule.impl``
  and ``core.lowering`` dispatches on that field alone — no ``backend == "tpu"`` flag or shape threshold re-derives
  the choice downstream.

In ``mode="opaque"`` the pipeline instead calls ``assign_early_heuristics``
*before* any optimization pass, reproducing stock-XLA behaviour for the A/B
benchmark.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import numpy as np

from .ir import LIBRARY_OPS, Node, TaskGraph, dtype_bytes
from repro.kernels.flash_attention.ops import (attention_cost,
                                               kernel_unsupported)
from repro.kernels.fused_matmul.ops import matmul_cost, row_block
from repro.kernels.linear_scan.ops import SAFE_CHUNK, scan_cost
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.ssm_state_step import ops as ssm_ops


@dataclass(frozen=True)
class CostModel:
    """Roofline constants of one target.  The defaults are TPU v5e's; the
    device-derived table is ``COST_MODELS``."""
    name: str = "tpu_v5e"
    peak_flops: float = 197e12          # bf16 FLOP/s per chip
    hbm_bw: float = 819e9               # bytes/s per chip
    ici_bw: float = 50e9                # bytes/s per link
    vmem_bytes: int = 128 * 1024 * 1024 # ~128MiB VMEM per core (v5e ~128MB)
    mxu: int = 128                      # systolic array edge
    # Small-task serialization threshold: parallel work below this many FLOPs
    # per task is not worth a grid/mesh binding (analogue of spawn overhead).
    grain_flops: float = 2.0 * 128 * 128 * 128
    # Bandwidth-bound analogue for data-movement ops (cache reads/writes):
    # below this many bytes per task a parallel binding can't pay for itself.
    grain_bytes: float = 1 << 20
    # scan-vs-unroll: unroll layer loops at or below this trip count
    unroll_max_trip: int = 4
    # GQA materialized attention: "repeat" (BLAS-friendly K/V copy) is worth
    # it only while the copy time stays under this fraction of the
    # attention's compute time (decode against a long cache flips to the
    # grouped einsum — KV bytes dominate there).
    gqa_repeat_frac: float = 0.25
    # Per-serial-step dispatch overhead (a lax.scan trip, a sequential
    # library call) — the literal Cilk spawn-overhead analogue the impl
    # registry charges blockwise/chunked candidates per step.  This is
    # what makes a tiny attention pick the materialized einsum over the
    # online-softmax scan: one scan step costs more than streaming a
    # 16x16 score matrix.
    spawn_s: float = 1e-6
    # Round-trips over the fp32 score matrix charged to impls that
    # materialize it (einsum-write, mask, softmax, PV-read).
    score_passes_materialized: float = 4.0
    # Same for the fused single-expression composite (``ref``): on the TPU
    # target the fused score tiles stay VMEM-resident (~1 pass); a CPU has
    # no scratchpad, so the fused form still walks the score matrix
    # through the cache hierarchy like the materialized one does.
    score_passes_fused: float = 1.0
    # --- remat arm (training fwd/bwd boundary) ------------------------
    # Storing an activation across the forward/backward boundary costs one
    # HBM write at the end of the forward plus one read in the backward;
    # rematerializing it costs the node's own FLOPs plus re-reading its
    # inputs.  ``remat_store_roundtrips`` is the round-trip count charged
    # to the store side (2.0 = write + read); ``remat_bias`` scales the
    # recompute side (>1 biases toward storing — recompute serializes the
    # backward, which a pure roofline undercounts).
    remat_store_roundtrips: float = 2.0
    remat_bias: float = 1.0


CPU_COST_MODEL = CostModel(name="cpu_host", peak_flops=5e10, hbm_bw=2e10,
                           ici_bw=1e9, vmem_bytes=1 << 21, mxu=8,
                           grain_flops=1 << 14, grain_bytes=1 << 16,
                           unroll_max_trip=8, spawn_s=2e-5,
                           score_passes_fused=4.0)

#: Cost model per ``jax.devices()[0].device_kind``.  TPU v5e peaks from
#: Google Cloud's "TPU v5e" documentation: 197 TFLOP/s bf16, 819 GB/s and
#: 16 GiB of HBM per chip.  The CPU entry serves tests and CPU rehearsals.
COST_MODELS: dict[str, CostModel] = {
    "TPU v5 lite": CostModel(),
    "cpu": CPU_COST_MODEL,
}


def cost_model_for(device_kind: Optional[str] = None) -> CostModel:
    """The cost model of ``device_kind``, by default the kind of the device
    this process computes on; a kind with no entry raises, since its peaks
    would otherwise be assumed."""
    kind = device_kind or _device_kind()
    try:
        return COST_MODELS[kind]
    except KeyError:
        raise ValueError(
            f"no cost model for device kind {kind!r}; add its peaks "
            f"to core.schedule.COST_MODELS (known: {sorted(COST_MODELS)})"
        ) from None


@functools.cache
def _device_kind() -> str:
    # read once: the cost model sits on the op-dispatch hot path (every
    # cache key)
    return jax.devices()[0].device_kind


def _align(x: int, m: int) -> int:
    return max(m, (x // m) * m) if x >= m else x


def pick_matmul_tiles(m: int, n: int, k: int, dtype: str, cm: CostModel) -> dict[str, int]:
    """Strip-mining for a GEMM: MXU-aligned (bm, bn, bk) whose working set
    (A-tile + B-tile + C-tile in fp32 accum) fits in a VMEM budget.

    Greedy: start from (128, 128, k) and shrink bk, then grow bm/bn while the
    footprint allows — large bk amortizes the C-tile writeback, large bm/bn
    amortize A/B reloads (classic blocking arithmetic)."""
    eb = dtype_bytes(dtype)
    budget = cm.vmem_bytes // 3  # leave room for double-buffering + epilogue operands
    bm = min(_align(m, cm.mxu), 512)
    bn = min(_align(n, cm.mxu), 512)
    bk = min(_align(k, cm.mxu), 2048)

    def footprint(bm, bn, bk):
        return eb * (bm * bk + bk * bn) + 4 * bm * bn  # fp32 accumulator

    while footprint(bm, bn, bk) > budget and bk > cm.mxu:
        bk //= 2
    while footprint(bm, bn, bk) > budget and (bm > cm.mxu or bn > cm.mxu):
        if bm >= bn and bm > cm.mxu:
            bm //= 2
        elif bn > cm.mxu:
            bn //= 2
        else:
            break
    return {"bm": min(bm, max(m, 1)), "bn": min(bn, max(n, 1)),
            "bk": min(bk, max(k, 1))}


def _weight_ndim(g: TaskGraph, node: Node) -> int:
    """Rank of a matmul node's weight (2 when ``g`` does not hold it)."""
    w = node.inputs[1] if len(node.inputs) > 1 else None
    return len(g.nodes[w].ttype.shape) if w in g.nodes else 2


def matmul_rows(g: TaskGraph, node: Node) -> int:
    """Rows of the GEMM a matmul node runs.  With a 2-D weight the fused
    kernel flattens every leading dim of the activation into rows, so a
    ``[slots, 1, d]`` decode activation is ``slots`` rows, not 1; a stacked
    or batched weight keeps ``shape[-2]`` rows per batch."""
    shape = node.ttype.shape
    if _weight_ndim(g, node) == 2:
        return int(np.prod(shape[:-1]))
    return shape[-2]


def matmul_tiles(g: TaskGraph, node: Node, cm: CostModel) -> dict[str, int]:
    """A matmul node's tiles, picked for the rows the kernel sees: one row
    block up to ``pick_matmul_tiles``' cap, so a decode GEMM streams its
    weight once a call."""
    return pick_matmul_tiles(matmul_rows(g, node), node.ttype.shape[-1],
                             node.attrs["k"], node.ttype.dtype, cm)


def note_weight_passes(g: TaskGraph, node: Node) -> None:
    """Note on a fused-kernel GEMM how often one call streams its weight
    from HBM: once per row block of the kernel's grid."""
    if node.schedule.impl != "fused_kernel":
        return
    rows = matmul_rows(g, node)
    bm = row_block(node.schedule.tile["bm"], rows)
    node.schedule.notes.append(
        f"weight passes: {-(-rows // bm)} (rows {rows}, bm {bm})")


def pick_attention_tiles(s_q: int, s_kv: int, d: int, dtype: str, cm: CostModel) -> dict[str, int]:
    """Flash-attention blocking: (block_q, block_kv) sized so q/k/v tiles +
    running stats fit VMEM, MXU-aligned."""
    eb = dtype_bytes(dtype)
    budget = cm.vmem_bytes // 4
    bq = min(_align(s_q, cm.mxu), 512)
    bkv = min(_align(s_kv, cm.mxu), 1024)
    while eb * (bq * d + 2 * bkv * d) + 4 * bq * (bkv + d) > budget and bkv > cm.mxu:
        bkv //= 2
    while eb * (bq * d + 2 * bkv * d) + 4 * bq * (bkv + d) > budget and bq > cm.mxu:
        bq //= 2
    return {"bq": min(bq, max(s_q, 1)), "bkv": min(bkv, max(s_kv, 1))}


def pick_scan_chunk(seq: int, d_k: int, d_v: int, dtype: str,
                    cm: CostModel) -> int:
    """Linear-scan chunk size: the largest chunk whose per-task working set
    (q/k/w/v chunk tiles + the fp32 [C,C] factored score block + the
    [Dk,Dv] carry) fits a VMEM budget, capped at the numerically-exact
    bound for the factored score matmul (``kernels/linear_scan/ops.
    SAFE_CHUNK`` — imported, so the cap can't drift from the kernel's)."""
    eb = dtype_bytes(dtype)
    # the [Dk,Dv] carry is chunk-independent (subtract it, but never let a
    # huge state zero the budget — the kernel streams it regardless)
    budget = max(cm.vmem_bytes // 4 - 4 * d_k * d_v, cm.vmem_bytes // 32)
    c = SAFE_CHUNK
    while c > 1 and eb * c * (3 * d_k + d_v) + 4 * c * c > budget:
        c //= 2
    return max(1, min(c, max(seq, 1)))


def _dim_shard(node: Node, d: int, mesh_axes: Optional[dict]) -> int:
    """Mesh-axis product this output dim is split over (1 if unsharded)."""
    if not mesh_axes or node.sharding is None or d >= len(node.sharding):
        return 1
    entry = node.sharding[d]
    if entry is None:
        return 1
    f = 1
    for ax in (entry if isinstance(entry, tuple) else (entry,)):
        f *= mesh_axes.get(ax, 1)
    return f


def shard_factor(node: Node, mesh_axes: Optional[dict] = None) -> float:
    """Number of shards this node's output is split into: the product of
    the mesh-axis sizes named by its ``sharding`` annotation.  Per-device
    work/bytes of a partitioned node are the logical totals divided by
    this factor — the cost model must reason per shard, or a node that is
    tiny per device would still look big enough to parallelize."""
    if not mesh_axes or node.sharding is None:
        return 1.0
    f = 1.0
    for d in range(len(node.sharding)):
        f *= _dim_shard(node, d, mesh_axes)
    return max(f, 1.0)


def pick_gqa_impl(node: Node, cm: CostModel, backend: str,
                  mesh_axes: Optional[dict] = None) -> str:
    """GQA materialized attention: grouped einsum (no K/V copy) vs
    ``jnp.repeat`` of K/V to full head count (BLAS-shaped batched GEMM).

    Backend-aware cost choice instead of the old hardcode: the repeat
    moves ``(grp-1) * 2 * |K|`` extra bytes; on CPU BLAS that buys a
    measurably faster contraction (spot: ~1.3x at B=8,S=256,Hq=8,Hkv=2,
    D=64), so repeat wins while the copy time stays under
    ``gqa_repeat_frac`` of the attention's compute time.  Decode against a
    long cache (S=1, KV bytes dominate) and the TPU target (flash kernel /
    grouped contraction, no HBM copy wanted) stay grouped.

    ``mesh_axes`` makes the comparison per-shard — and the two sides
    scale DIFFERENTLY: compute divides by the full shard factor of the
    output, while the K/V repeat-copy only shrinks along dims where K/V
    itself is partitioned (the batch dim, and the head dim only when
    ``Hkv`` divides that axis).  Small-``Hkv`` TP is the common case:
    q-heads shard over ``model`` but K/V stays replicated, so per-device
    compute drops while the copy doesn't — sharding biases the choice
    toward grouped, exactly the physical intuition."""
    b, s, h, d = node.attrs["q_shape"]
    hkv = node.attrs.get("kv_heads", h)
    if backend == "tpu" or not hkv or hkv >= h:
        return "grouped"
    grp = h // hkv
    eb = dtype_bytes(node.ttype.dtype)
    skv = node.attrs["kv_len"]
    # output dims are q-shaped [B, S, H, D]: dim 0 = batch, dim 2 = heads
    h_split = _dim_shard(node, 2, mesh_axes)
    kv_shard = _dim_shard(node, 0, mesh_axes) * (
        h_split if hkv % max(h_split, 1) == 0 else 1)
    copy_s = 2.0 * (grp - 1) * b * skv * hkv * d * eb / cm.hbm_bw \
        / max(kv_shard, 1)
    compute_s = node.flops() / cm.peak_flops / shard_factor(node, mesh_axes)
    return "repeat" if copy_s <= cm.gqa_repeat_frac * compute_s else "grouped"


# ---------------------------------------------------------------------------
# Implementation registry (the TapirXLA selection point): every library op
# has a list of candidate lowerings, each costed by the same roofline the
# rest of the scheduler uses, and ``assign_schedules`` binds the argmin to
# ``node.schedule.impl``.  ``core.lowering`` dispatches on that field alone.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ImplCandidate:
    """One candidate lowering of a library op: its roofline time per shard,
    or ``None`` with a reason when the backend/shape rules it out."""
    name: str
    cost_s: Optional[float]
    why: str = ""


def _kernel_unavailable(backend: str,
                        mesh_axes: Optional[dict]) -> Optional[str]:
    """Why no Pallas kernel can run here (None when one can): Mosaic
    kernels need the TPU target, and a program under an ambient mesh is
    partitioned by GSPMD, which cannot partition their custom call (that
    needs a shard_map)."""
    if backend != "tpu":
        return "pallas kernel needs the TPU target"
    if mesh_axes:
        return "GSPMD cannot partition a Mosaic kernel over the mesh"
    return None


def _fmt_s(t: float) -> str:
    return f"{t * 1e6:.1f}us" if t < 1e-3 else f"{t * 1e3:.2f}ms"


def attention_candidates(g: TaskGraph, node: Node, cm: CostModel,
                         backend: str, mesh_axes: Optional[dict] = None
                         ) -> list[ImplCandidate]:
    """Five ways to run scaled-dot-product attention, costed per shard:

    * ``flash_kernel``       — Pallas flash kernel (TPU, S>1, no bias, and
                               causal or KV a whole number of blocks)
    * ``blockwise``          — online-softmax lax.scan over KV blocks; never
                               materializes scores but pays ``spawn_s`` per
                               block step (the Cilk spawn-overhead analogue)
    * ``materialized_repeat``— fp32 score matrix, K/V repeated to full head
                               count (BLAS-shaped batched GEMM; CPU + GQA)
    * ``materialized_grouped``— fp32 score matrix, grouped contraction
                               (no K/V copy; grouped-einsum penalty on GQA)
    * ``ref``                — single fused composite expression

    The repeat-vs-grouped comparison reduces to exactly the inequality
    ``pick_gqa_impl`` tests (same copy bytes over the same kv shard vs the
    same ``gqa_repeat_frac`` compute fraction), so the two stay consistent
    by construction."""
    b, sq, h, d = node.attrs["q_shape"]
    skv = node.attrs["kv_len"]
    hkv = node.attrs.get("kv_heads", h) or h
    grp = h // hkv
    eb = dtype_bytes(node.ttype.dtype)
    has_bias = len(node.inputs) > 3
    shard = shard_factor(node, mesh_axes)
    # the K/V repeat-copy shards like pick_gqa_impl's kv_shard (batch, and
    # heads only when Hkv divides the head split), NOT the full factor
    h_split = _dim_shard(node, 2, mesh_axes)
    kv_shard = max(_dim_shard(node, 0, mesh_axes)
                   * (h_split if hkv % max(h_split, 1) == 0 else 1), 1)
    tile = node.schedule.tile or pick_attention_tiles(
        sq, skv, d, node.ttype.dtype, cm)
    bkv = tile.get("bkv", 1024)
    compute_s = node.flops() / cm.peak_flops / shard
    flash_why = kernel_unsupported(skv, node.attrs.get("causal", False),
                                   has_bias, bkv)

    def base(impl: str):
        c = attention_cost(b, sq, skv, h, hkv, d, eb, impl, block_kv=bkv)
        return c, (c["flops"] / cm.peak_flops + c["io_bytes"] / cm.hbm_bw) / shard

    out: list[ImplCandidate] = []
    no_kernel = _kernel_unavailable(backend, mesh_axes)
    if no_kernel:
        out.append(ImplCandidate("flash_kernel", None, no_kernel))
    elif sq <= 1:
        out.append(ImplCandidate("flash_kernel", None,
                                 "decode (S=1): kernel q-grid degenerates"))
    elif flash_why:
        out.append(ImplCandidate("flash_kernel", None, flash_why))
    else:
        _, t = base("flash_kernel")
        out.append(ImplCandidate("flash_kernel", t))

    if has_bias:
        out.append(ImplCandidate("blockwise", None, "no bias operand"))
    else:
        c, t = base("blockwise")
        out.append(ImplCandidate("blockwise",
                                 t + c["steps"] * cm.spawn_s / shard))

    if grp <= 1:
        out.append(ImplCandidate("materialized_repeat", None,
                                 "no K/V head group to repeat"))
    elif backend == "tpu":
        out.append(ImplCandidate("materialized_repeat", None,
                                 "HBM repeat-copy unwanted on TPU"))
    else:
        c, t = base("materialized_repeat")
        t += c["score_bytes"] * cm.score_passes_materialized / cm.hbm_bw / shard
        t += c["copy_bytes"] / cm.hbm_bw / kv_shard
        out.append(ImplCandidate("materialized_repeat", t))

    c, t = base("materialized_grouped")
    t += c["score_bytes"] * cm.score_passes_materialized / cm.hbm_bw / shard
    if grp > 1:
        t += cm.gqa_repeat_frac * compute_s  # grouped-contraction penalty
    out.append(ImplCandidate("materialized_grouped", t))

    c, t = base("ref")
    t += c["score_bytes"] * cm.score_passes_fused / cm.hbm_bw / shard
    if grp > 1:
        t += cm.gqa_repeat_frac * compute_s
    out.append(ImplCandidate("ref", t))
    return out


def paged_attention_candidates(g: TaskGraph, node: Node, cm: CostModel,
                               backend: str, mesh_axes: Optional[dict] = None
                               ) -> list[ImplCandidate]:
    """Decode attention over the page pool, costed per shard at the bound
    (every slot's whole view; the live length is data):

    * ``paged_kernel`` — Pallas kernel reading the live pages in place
                         (TPU, no mesh, shapes it takes)
    * ``gathered``     — copy each slot's view out of the pool, then the
                         masked composite attention over it"""
    b, s, h, d = node.attrs["q_shape"]
    hkv, page_len, pps = (node.attrs["kv_heads"], node.attrs["page_len"],
                          node.attrs["pps"])
    eb = dtype_bytes(g.nodes[node.inputs[1]].ttype.dtype)
    shard = shard_factor(node, mesh_axes)

    def roof(impl: str) -> float:
        c = paged_ops.paged_attention_cost(b, s, h, hkv, d, page_len, pps,
                                           eb, impl)
        return (c["flops"] / cm.peak_flops + (
            c["io_bytes"] + c["score_bytes"] * cm.score_passes_fused)
            / cm.hbm_bw) / shard

    why = _kernel_unavailable(backend, mesh_axes) or \
        paged_ops.kernel_unsupported(b, s, h, hkv, d, page_len, pps, eb)
    return [ImplCandidate("paged_kernel", None if why else
                          roof("paged_kernel"), why),
            ImplCandidate("gathered", roof("gathered"))]


def matmul_candidates(g: TaskGraph, node: Node, cm: CostModel,
                      backend: str, mesh_axes: Optional[dict] = None
                      ) -> list[ImplCandidate]:
    """``fused_kernel`` (Pallas GEMM, epilogue executed on the VMEM-resident
    accumulator tile — no epilogue round-trips) vs ``einsum`` (XLA dot; each
    unfused epilogue op re-walks the output through HBM)."""
    shape = node.ttype.shape
    m, n = shape[-2], shape[-1]
    k = node.attrs["k"]
    batch = int(np.prod(shape[:-2])) if len(shape) > 2 else 1
    eb = dtype_bytes(node.ttype.dtype)
    shard = shard_factor(node, mesh_axes)
    n_epi = len(node.epilogue)
    w_nd = _weight_ndim(g, node)

    def roof(impl: str) -> float:
        c = matmul_cost(batch, m, n, k, eb, impl, n_epilogue=n_epi)
        return (c["flops"] / cm.peak_flops + c["io_bytes"] / cm.hbm_bw) / shard

    out: list[ImplCandidate] = []
    no_kernel = _kernel_unavailable(backend, mesh_axes)
    if no_kernel:
        out.append(ImplCandidate("fused_kernel", None, no_kernel))
    elif w_nd != 2:
        out.append(ImplCandidate("fused_kernel", None,
                                 "stacked/batched weights (kernel takes 2-D W)"))
    else:
        out.append(ImplCandidate("fused_kernel", roof("kernel")))
    out.append(ImplCandidate("einsum", roof("einsum")))
    return out


def linear_scan_candidates(g: TaskGraph, node: Node, cm: CostModel,
                           backend: str, mesh_axes: Optional[dict] = None
                           ) -> list[ImplCandidate]:
    """``kernel`` (Pallas chunked scan, no per-chunk dispatch) vs ``chunked``
    (lax.scan over chunks: factored-score extra FLOPs + ``spawn_s`` per
    chunk) vs ``ref`` (element recurrence: ``spawn_s`` per *timestep*)."""
    seq = node.attrs["seq"]
    q_t = g.nodes[node.inputs[0]].ttype
    b, _, h, d_k = q_t.shape
    d_v = g.nodes[node.inputs[2]].ttype.shape[-1]
    eb = dtype_bytes(node.ttype.dtype)
    shard = shard_factor(node, mesh_axes)
    chunk = node.schedule.tile.get("chunk") or pick_scan_chunk(
        seq, d_k, d_v, node.ttype.dtype, cm)

    def roof(impl: str) -> float:
        c = scan_cost(b, seq, h, d_k, d_v, eb, impl, chunk=chunk)
        return (c["flops"] / cm.peak_flops + c["io_bytes"] / cm.hbm_bw
                + c["steps"] * cm.spawn_s) / shard

    out: list[ImplCandidate] = []
    no_kernel = _kernel_unavailable(backend, mesh_axes)
    if no_kernel:
        out.append(ImplCandidate("kernel", None, no_kernel))
    else:
        out.append(ImplCandidate("kernel", roof("kernel")))
    out.append(ImplCandidate("chunked", roof("chunked")))
    out.append(ImplCandidate("ref", roof("ref")))
    return out


def ssm_state_step_candidates(g: TaskGraph, node: Node, cm: CostModel,
                              backend: str, mesh_axes: Optional[dict] = None
                              ) -> list[ImplCandidate]:
    """One decode position of an SSM over per-slot state rows, costed per
    shard:

    * ``kernel``    — Pallas kernel updating the rows in place, one pass
                      over the state (TPU, no mesh, shapes it takes)
    * ``composite`` — the jnp update: XLA walks the state four times"""
    s, h, p, n = node.attrs["dims"]
    shard = shard_factor(node, mesh_axes)

    def roof(impl: str) -> float:
        c = ssm_ops.ssm_state_step_cost(s, h, p, n, impl)
        return (c["flops"] / cm.peak_flops + c["io_bytes"] / cm.hbm_bw) \
            / shard

    why = _kernel_unavailable(backend, mesh_axes) or \
        ssm_ops.kernel_unsupported(s, h, p, n)
    return [ImplCandidate("kernel", None if why else roof("kernel"), why),
            ImplCandidate("composite", roof("composite"))]


def conv2d_candidates(g: TaskGraph, node: Node, cm: CostModel,
                      backend: str, mesh_axes: Optional[dict] = None
                      ) -> list[ImplCandidate]:
    """conv2d has a single lowering today (XLA's general conv); registered
    anyway so the decision is observable in ``dump_schedule`` and future
    kernels slot into the same argmin."""
    shard = shard_factor(node, mesh_axes)
    io = float(np.prod(node.ttype.shape)) * dtype_bytes(node.ttype.dtype)
    for i in node.inputs:
        t = g.nodes[i].ttype
        io += float(np.prod(t.shape)) * dtype_bytes(t.dtype)
    return [ImplCandidate(
        "xla", (node.flops() / cm.peak_flops + io / cm.hbm_bw) / shard)]


# Candidate order is the tie-break: the roofline argmin is taken with a
# strict ``<``, so on an exact tie the EARLIER candidate wins (kernel over
# jnp, repeat over grouped — matching pick_gqa_impl's ``<=`` — and
# materialized over ref, today's CPU behaviour).
IMPL_REGISTRY: dict[str, Callable] = {
    "matmul": matmul_candidates,
    "attention": attention_candidates,
    "paged_attention": paged_attention_candidates,
    "linear_scan": linear_scan_candidates,
    "ssm_state_step": ssm_state_step_candidates,
    "conv2d": conv2d_candidates,
}


def pick_impl(g: TaskGraph, node: Node, cm: CostModel, backend: str,
              mesh_axes: Optional[dict] = None,
              forced: Optional[str] = None) -> None:
    """Cost every registered candidate for this library node, record the
    full table in ``schedule.impl_costs``, and bind the argmin (or the
    config-``forced`` name) to ``schedule.impl``."""
    cands = IMPL_REGISTRY[node.op](g, node, cm, backend, mesh_axes)
    node.schedule.impl_costs = {
        c.name: (c.cost_s if c.cost_s is not None else f"n/a ({c.why})")
        for c in cands}
    if forced is not None:
        for c in cands:
            if c.name == forced:
                if c.cost_s is None:
                    raise ValueError(
                        f"forced impl {forced!r} is unavailable for "
                        f"{node.op} node %{node.nid}: {c.why}")
                node.schedule.impl = forced
                node.schedule.notes.append(f"impl: {forced} (forced by config)")
                return
        raise ValueError(
            f"unknown impl {forced!r} for op {node.op!r}; candidates: "
            f"{[c.name for c in cands]}")
    best = None
    for c in cands:
        if c.cost_s is not None and (best is None or c.cost_s < best.cost_s):
            best = c
    node.schedule.impl = best.name
    n_avail = sum(1 for c in cands if c.cost_s is not None)
    node.schedule.notes.append(
        f"impl: {best.name} ({_fmt_s(best.cost_s)} roofline, argmin of "
        f"{n_avail}/{len(cands)} candidates)")


def pick_remat(g: TaskGraph, node: Node, cm: CostModel,
               policy: str = "auto") -> str:
    """Recompute-vs-store for a forward node whose output the backward
    consumes — the remat arm of the cost model.

    ``policy`` is the TrainConfig.remat hint:
      * "auto"  — roofline decision: store costs ``remat_store_roundtrips``
        HBM trips over the node's output bytes; recompute costs the node's
        FLOPs at peak plus re-streaming its input bytes.  Elementwise
        composites (norms, RoPE, residual adds) recompute nearly for free,
        GEMM/attention outputs are cheaper to store.
      * "none"  — store everything (no remat);
      * "full"  — recompute everything;
      * "dots"  — store library-op (GEMM-shaped) outputs only, the
        ``checkpoint_dots`` analogue.
    Either choice is bitwise-identical (recompute replays the exact same
    ops); the decision moves HBM bytes, never numerics."""
    if policy == "none":
        return "store"
    if policy == "full":
        return "recompute"
    if policy == "dots":
        return "store" if node.op in LIBRARY_OPS else "recompute"
    store_s = cm.remat_store_roundtrips * node.ttype.bytesize / cm.hbm_bw
    in_bytes = sum(g.nodes[i].ttype.bytesize for i in node.inputs
                   if i in g.nodes)
    recompute_s = cm.remat_bias * (node.flops() / cm.peak_flops
                                   + in_bytes / cm.hbm_bw)
    choice = "recompute" if recompute_s < store_s else "store"
    node.schedule.notes.append(
        f"remat: {choice} (store {store_s*1e6:.1f}us vs recompute "
        f"{recompute_s*1e6:.1f}us)")
    return choice


# ---------------------------------------------------------------------------
# Late scheduling (tapir mode)
# ---------------------------------------------------------------------------


def assign_schedules(g: TaskGraph, cm: CostModel, backend: str = "tpu",
                     mesh_axes: Optional[dict] = None,
                     force_impl: Optional[tuple] = None) -> TaskGraph:
    """Bind schedules on the optimized graph.

    Policy (per parallel dim, largest extent first):
      1. dims already bound by the spawn pass to a mesh axis keep it;
      2. dims with per-task work >= grain_flops become Pallas ``grid`` axes;
      3. trailing dims of size >= 8 become ``vector`` (VPU lanes);
      4. everything else is ``serial`` — small-task serialization.
    Exposed library ops additionally get strip-mined tiles and their
    IMPLEMENTATION from the roofline argmin over ``IMPL_REGISTRY``
    (``pick_impl`` -> ``node.schedule.impl``); unexposed library ops are
    bound to the sealed ``"opaque"`` lowering.  ``mesh_axes`` (axis name ->
    size, from the ambient mesh) makes every cost PER-SHARD: a node whose
    ``sharding`` partitions it over mesh axes moves/computes 1/shard per
    device, so grain-size serialization and every impl choice divide by the
    shard factor.  ``force_impl`` — ``((op_kind, impl_name), ...)`` pairs —
    overrides the argmin per op kind (unknown/unavailable names raise)."""
    forced = dict(force_impl or ())
    cache_ops = ("dynamic_update_slice", "dynamic_slice", "index", "slice",
                 "gather", "scatter")
    for nid in g.topo_order():
        node = g.nodes[nid]
        if node.op in ("input", "const"):
            continue
        shard = shard_factor(node, mesh_axes)
        work = (node.flops() + 1.0) / shard
        shape = node.ttype.shape
        # data-movement ops have no flops; their cost (and the grain for
        # serialization) is bytes moved, not arithmetic
        moved = None
        if node.op in cache_ops:
            if node.op == "dynamic_update_slice":
                upd_t = g.nodes[node.inputs[1]].ttype
            elif node.op == "scatter":
                # the update is the last input (after buffer + index
                # operands; zero-init scatters have no buffer input)
                upd_t = g.nodes[node.inputs[-1]].ttype
            else:
                upd_t = None
            moved = node.bytes_moved(upd_t) / shard
            node.schedule.notes.append(
                f"cache-op {moved:.0f}B moved"
                + (f" (1/{shard:.0f} per shard)" if shard > 1 else "")
                + (" in-place (buffer donated)" if node.donates is not None
                   else ""))
        grain = cm.grain_bytes if moved is not None else cm.grain_flops
        work = moved if moved is not None else work
        for d in node.pdims:
            if d in node.schedule.dim_binding:
                continue  # spawn pass already bound (e.g. mesh:data)
            extent = shape[d] if d < len(shape) else 1
            per_task = work / max(extent, 1)
            if per_task >= grain:
                node.schedule.dim_binding[d] = "grid"
            elif d == len(shape) - 1 and extent >= 8:
                node.schedule.dim_binding[d] = "vector"
            else:
                node.schedule.dim_binding[d] = "serial"
                node.schedule.notes.append(
                    f"small-task serialized dim{d} (per-task {per_task:.0f} "
                    + ("bytes)" if moved is not None else "flops)"))
        if node.op == "matmul":
            node.schedule.tile = matmul_tiles(g, node, cm)
        elif node.op == "attention":
            b, s, h, d_ = node.attrs["q_shape"]
            node.schedule.tile = pick_attention_tiles(s, node.attrs["kv_len"], d_,
                                                      node.ttype.dtype, cm)
            # the materialized-flavour decision, kept as a node attr for
            # observability (the registry's repeat/grouped costs reduce to
            # the same inequality, so the two never disagree)
            node.attrs["gqa_impl"] = pick_gqa_impl(node, cm, backend,
                                                   mesh_axes=mesh_axes)
            if node.attrs["gqa_impl"] == "repeat":
                node.schedule.notes.append("gqa: repeat K/V (BLAS wins, "
                                           "copy cost amortized)")
        elif node.op == "paged_attention":
            a = node.attrs
            eb = dtype_bytes(g.nodes[node.inputs[1]].ttype.dtype)
            node.schedule.tile = {"pages_per_block":
                                  paged_ops.pick_pages_per_block(
                                      a["page_len"], a["kv_heads"],
                                      a["q_shape"][-1], eb, a["pps"])}
        elif node.op == "linear_scan":
            # chunk the sequence; carry crosses chunks (the join).  Derived
            # from CostModel.vmem_bytes, capped at the numerically-exact
            # bound for the factored score matmul (SAFE_CHUNK).
            seq = node.attrs["seq"]
            q_t = g.nodes[node.inputs[0]].ttype
            d_v = g.nodes[node.inputs[2]].ttype.shape[-1]
            node.schedule.tile = {"chunk": pick_scan_chunk(
                seq, q_t.shape[-1], d_v, node.ttype.dtype, cm)}
        if node.op in LIBRARY_OPS:
            if node.attrs.get("exposed", False):
                pick_impl(g, node, cm, backend, mesh_axes=mesh_axes,
                          forced=forced.get(node.op))
                if node.op == "matmul":
                    note_weight_passes(g, node)
            else:
                node.schedule.impl = "opaque"
        node.schedule.serialized = all(
            b == "serial" for b in node.schedule.dim_binding.values()) and bool(
            node.schedule.dim_binding)
    return g


# ---------------------------------------------------------------------------
# Early heuristics (opaque mode — the stock-XLA control)
# ---------------------------------------------------------------------------


def assign_early_heuristics(g: TaskGraph, cm: CostModel) -> TaskGraph:
    """Reproduce the baseline: each op partitioned in isolation, *before*
    optimization, with a fixed per-op rule (outermost dim parallel, fixed
    256-row tiles, no epilogue awareness, no kernel lowering)."""
    for node in g.nodes.values():
        if node.op in ("input", "const"):
            continue
        for d in node.pdims:
            node.schedule.dim_binding[d] = "grid" if d == 0 else "serial"
        if node.op in ("matmul", "attention", "conv2d"):
            node.schedule.tile = {"bm": 256, "bn": 256, "bk": 256}
        if node.op in LIBRARY_OPS:
            node.schedule.impl = "opaque"  # sealed library call, no registry
        node.schedule.notes.append("early-heuristic (opaque mode)")
    return g
