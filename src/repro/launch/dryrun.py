import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This proves the distribution config is coherent without hardware: the
production mesh is built from 512 placeholder host devices (the two lines
above MUST precede any jax import), every model input is a
ShapeDtypeStruct (nothing is allocated), and ``jit(...).lower().compile()``
runs the full GSPMD partitioner + XLA pipeline.  The compiled artifact
yields ``memory_analysis()`` (fits-per-device evidence), ``cost_analysis()``
(FLOPs / HBM bytes for the roofline), and the optimized HLO text from which
collective traffic is extracted (launch.hlo_stats).

Usage:
  python -m repro.launch.dryrun --arch qwen2_5_3b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
  ... --mode opaque            (paper A/B control)
  ... --sp --microbatches 16   (perf-iteration knobs)
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, applicable, get_config
from repro.launch.hlo_cost import analyze
from repro.launch.hlo_stats import CHIPS_PER_POD, Roofline
from repro.launch.mesh import make_production_mesh
from repro.models.base import get_model
from repro.optim import AdamWConfig
from repro.serve import ServeConfig, cache_shardings, make_decode_step
from repro.train import TrainConfig, make_state_specs, make_train_step
from repro.dist.sharding import (batch_pspec, configure_rules,
                                 param_shardings)
from repro.core.tapir import TapirConfig, use


#: the chip whose cost model schedules the planned programs
TARGET = "TPU v5 lite"


def _attach(sds, sharding):
    return jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sharding)


def _batch_sds(ispecs: dict, mesh) -> dict:
    out = {}
    for k, s in ispecs.items():
        spec = batch_pspec(mesh, ndim=len(s.shape), batch_size=s.shape[0])
        out[k] = _attach(s, NamedSharding(mesh, spec))
    return out


def _default_microbatches(arch: str, shape) -> int:
    if shape.kind != "train":
        return 1
    big = get_config(arch).n_params() > 20e9
    return 8 if big else 4


def model_flops(cfg, shape) -> float:
    n = cfg.n_active_params() if cfg.family == "moe" else cfg.n_params()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch        # decode: one token


def build_lowered(arch: str, shape_name: str, *, multi_pod: bool,
                  mode: str = "tapir", strategy: str | None = None,
                  microbatches: int | None = None, remat: str = "full",
                  sp: bool = False, bf16_partials: bool = False,
                  bf16_params: bool = False):
    """Returns (lowered, meta dict)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    model = get_model(cfg)
    shape = SHAPES[shape_name]
    strategy = strategy or ("fsdp_tp" if cfg.n_params() > 10e9 else "tp")
    mb = microbatches if microbatches is not None \
        else _default_microbatches(arch, shape)

    prev_rules = configure_rules(seq="model") if sp else None
    try:
        with jax.set_mesh(mesh):
            if shape.kind == "train":
                tcfg = TrainConfig(mode=mode, strategy=strategy,
                                   remat=remat, microbatches=mb,
                                   target=TARGET,
                                   bf16_partials=bf16_partials,
                                   bf16_params_in_loss=bf16_params)
                step, state_sh, _ = make_train_step(
                    model, AdamWConfig(), mesh, tcfg)
                state_sds, _ = make_state_specs(model, mesh, AdamWConfig(),
                                                strategy)
                ispecs = model.input_specs(shape.seq_len, shape.global_batch,
                                           "train")
                lowered = step.lower(state_sds, _batch_sds(ispecs, mesh))
            else:
                scfg = ServeConfig(mode=mode, strategy="tp",
                                   max_len=shape.seq_len, target=TARGET)
                p_sh = param_shardings(model.param_axes(), model.param_sds(),
                                       mesh, strategy="tp")
                p_sds = jax.tree_util.tree_map(_attach, model.param_sds(),
                                               p_sh)
                clen = model.cache_len(shape.seq_len, shape.kind)
                c_sh = cache_shardings(model, mesh, shape.global_batch,
                                       clen)
                c_sds = jax.tree_util.tree_map(
                    _attach, model.cache_specs(shape.global_batch, clen),
                    c_sh)
                if shape.kind == "decode":
                    step, _ = make_decode_step(model, mesh, scfg)
                    tok = _attach(
                        jax.ShapeDtypeStruct((shape.global_batch, 1),
                                             jnp.int32),
                        NamedSharding(mesh, batch_pspec(
                            mesh, 2, batch_size=shape.global_batch)))
                    lowered = step.lower(p_sds, tok, c_sds)
                else:  # prefill
                    ispecs = model.input_specs(shape.seq_len,
                                               shape.global_batch, "prefill")
                    bsds = _batch_sds(ispecs, mesh)
                    extra_keys = [k for k in bsds if k != "tokens"]
                    tap = scfg.tapir_config()

                    def prefill(params, tokens, cache, extras):
                        with use(tap):
                            if extra_keys:
                                return model.prefill(params, tokens, cache,
                                                     **extras)
                            return model.prefill(params, tokens, cache)

                    step = jax.jit(prefill, donate_argnums=(2,))
                    extras = {k: bsds[k] for k in extra_keys}
                    lowered = step.lower(p_sds, bsds["tokens"], c_sds, extras)
    finally:
        if prev_rules:
            configure_rules(**prev_rules)

    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "chips": 512 if multi_pod else 256,
            "mode": mode, "strategy": strategy, "microbatches": mb,
            "remat": remat, "sp": sp, "bf16_partials": bf16_partials,
            "bf16_params": bf16_params, "kind": shape.kind}
    return lowered, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, **kw) -> dict:
    shape = SHAPES[shape_name]
    ok, reason = applicable(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skip", "reason": reason}
    t0 = time.time()
    try:
        lowered, meta = build_lowered(arch, shape_name, multi_pod=multi_pod,
                                      **kw)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        ca = compiled.cost_analysis() or {}
        try:
            ma = compiled.memory_analysis()
            mem = {
                "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "generated_code_bytes":
                    int(getattr(ma, "generated_code_size_in_bytes", 0)),
            }
        except Exception:
            mem = {}

        hlo = compiled.as_text()
        cost = analyze(hlo)   # loop-aware: while bodies scaled by trip count
        cfg = get_config(arch)
        rl = Roofline(flops_per_dev=cost.flops,
                      hbm_bytes_per_dev=cost.bytes,
                      bytes_ici=cost.coll_ici, bytes_dcn=cost.coll_dcn,
                      chips=meta["chips"], coll_counts=cost.coll_counts,
                      model_flops=model_flops(cfg, shape))
        res = {**meta, "status": "ok", "t_lower_s": round(t_lower, 1),
               "t_compile_s": round(t_compile, 1), "memory": mem,
               "hlo_bytes": len(hlo), "unknown_trip": cost.unknown_trip,
               "xla_flops_per_dev": float(ca.get("flops", 0.0)),
               **rl.summary()}
        return res
    except Exception as e:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "fail", "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mode", default="tapir", choices=["tapir", "opaque"])
    ap.add_argument("--strategy", default=None, choices=[None, "tp", "fsdp_tp"])
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--remat", default="full",
                    choices=["none", "dots", "full"])
    ap.add_argument("--sp", action="store_true",
                    help="sequence-parallel residual stream")
    ap.add_argument("--bf16-partials", action="store_true",
                    help="bf16 TP all-reduce payloads")
    ap.add_argument("--bf16-params", action="store_true",
                    help="cast params to bf16 before loss (bf16 FSDP "
                         "gathers)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    cells = []
    archs = ARCH_IDS if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                res = run_cell(arch, shape, multi_pod=mp, mode=args.mode,
                               strategy=args.strategy,
                               microbatches=args.microbatches,
                               remat=args.remat, sp=args.sp,
                               bf16_partials=args.bf16_partials,
                               bf16_params=args.bf16_params)
                tag = f"_{args.tag}" if args.tag else ""
                fn = os.path.join(
                    args.out,
                    f"{arch}_{shape}_{res['mesh'].replace('x','-')}{tag}.json")
                with open(fn, "w") as f:
                    json.dump(res, f, indent=1)
                line = {k: v for k, v in res.items()
                        if k in ("arch", "shape", "mesh", "status",
                                 "bottleneck", "t_compute_s", "t_memory_s",
                                 "t_collective_s", "roofline_fraction",
                                 "t_compile_s", "error", "reason")}
                print(json.dumps(line))


if __name__ == "__main__":
    main()
