"""End-to-end training driver.

Glues together: config registry -> model -> data pipeline -> distributed
train step (pjit) -> checkpoint manager -> fault-tolerant loop.  On this
CPU container it drives the reduced smoke configs end-to-end; pointed at a
TPU slice the same driver runs the full configs (the mesh adapts to
``jax.devices()``).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_5_3b --smoke \
        --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import json
import logging
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.data import DataConfig, TokenPipeline
from repro.dist.fault import FaultTolerantLoop
from repro.models.base import get_model
from repro.optim import AdamWConfig
from repro.train import (TrainConfig, init_state, make_region_train_step,
                         make_train_step)

log = logging.getLogger("repro.train")


def make_mesh_for_devices(min_model: int = 1):
    """Best-effort mesh over whatever devices exist."""
    n = len(jax.devices())
    if n == 1:
        return None
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0 and m >= min_model:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2_5_3b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-feasible)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mode", default="tapir", choices=["tapir", "opaque"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default=None,
                    choices=["none", "dots", "full", "auto"],
                    help="default: none on the per-op path, auto "
                         "(roofline) with --capture-step")
    ap.add_argument("--capture-step", action="store_true",
                    help="run the region-captured training step (joint "
                         "fwd+bwd task graph, donated state) instead of "
                         "the per-op jax.grad path")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = get_model(cfg)
    log.info("arch=%s family=%s params=%.2fM", cfg.name, cfg.family,
             cfg.n_params() / 1e6)

    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(args.steps // 10, 1))
    mesh = make_mesh_for_devices()
    remat = args.remat or ("auto" if args.capture_step else "none")
    tcfg = TrainConfig(mode=args.mode, strategy="tp", remat=remat,
                       microbatches=args.microbatches)

    if args.capture_step:
        # region-captured step: ONE joint fwd+bwd program, compiled on the
        # first call and replayed from the program cache after; remat is a
        # roofline schedule decision ("auto") unless the flag forces it,
        # and params + optimizer state are donated through the program.
        step_fn, shardings = make_region_train_step(model, opt_cfg,
                                                    mesh=mesh, cfg=tcfg)
        state = init_state(model, opt_cfg, jax.random.PRNGKey(0), mesh)
    elif mesh is not None:
        step_fn, shardings, _ = make_train_step(model, opt_cfg, mesh, tcfg)
        state = init_state(model, opt_cfg, jax.random.PRNGKey(0), mesh)
    else:
        shardings = None
        tap = tcfg.tapir_config()

        def raw_step(state, batch):
            from repro.core.tapir import use
            from repro.optim import adamw_update

            def loss_fn(p):
                with use(tap):
                    return model.loss(p, batch)

            loss, grads = jax.value_and_grad(loss_fn)(state["params"])
            p2, o2, m = adamw_update(state["params"], grads, state["opt"],
                                     opt_cfg)
            return {"params": p2, "opt": o2}, {"loss": loss, **m}

        step_fn = jax.jit(raw_step, donate_argnums=(0,))
        state = init_state(model, opt_cfg, jax.random.PRNGKey(0))

    pipe = TokenPipeline(DataConfig(seq_len=args.seq,
                                    global_batch=args.batch,
                                    vocab=cfg.vocab))

    def batch_at(step: int) -> dict:
        b = pipe.batch_at(step)
        specs = model.input_specs(args.seq, args.batch, "train")
        out = dict(b)
        for k, s in specs.items():     # stub modality frontends
            if k not in out:
                out[k] = np.zeros(s.shape, s.dtype)
        return out

    ckpt = CheckpointManager(args.ckpt_dir, keep_n=3, every=args.ckpt_every)
    start_step = 0
    if args.resume:
        try:
            state, start_step, _ = ckpt.restore_latest(state,
                                                       shardings=shardings)
            log.info("resumed from step %d", start_step)
        except FileNotFoundError:
            log.info("no checkpoint found; cold start")

    loop = FaultTolerantLoop(step_fn, ckpt, batch_at,
                             state_shardings=shardings)

    t0 = time.time()
    state, stats = loop.run(state, start_step, args.steps)
    dt = time.time() - t0
    tok_s = args.steps * args.batch * args.seq / max(dt, 1e-9)
    log.info("done: %d steps in %.1fs (%.0f tok/s) loss %.4f -> %.4f",
             stats.steps_run, dt, tok_s,
             stats.losses[0] if stats.losses else float("nan"),
             stats.losses[-1] if stats.losses else float("nan"))
    print(json.dumps({"steps": stats.steps_run, "tok_per_s": tok_s,
                      "first_loss": stats.losses[0] if stats.losses else None,
                      "last_loss": stats.losses[-1] if stats.losses else None,
                      "failures": stats.failures,
                      "straggler_steps": stats.straggler_steps}))
    return state, stats


if __name__ == "__main__":
    main()
