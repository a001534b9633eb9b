"""Exposed parallel linear-algebra library (the TapirXLA Eigen replacement).

Each kernel is a subpackage with three layers:
  kernel.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper: padding, vjp, interpret-mode fallback
  ref.py    — pure-jnp oracle the tests sweep against

Unlike an opaque library call, these implementations carry *open epilogue
slots*: the fusion pass folds the calling context's elementwise tail into
the kernel body (TapirXLA SIII, "Exposing parallel linear-algebra routines").
"""
from . import flash_attention, fused_matmul, linear_scan, paged_attention

__all__ = ["flash_attention", "fused_matmul", "linear_scan",
           "paged_attention"]
