"""Bring-up smoke run on the chip: qwen2_5_3b at its published widths.

    python3 chip_smoke.py             # one chip: serve phase, then train phase
    python3 chip_smoke.py --chips 4   # four chips: TP-mesh serve vs one chip

Serve drives ``ServingEngine.run`` as ``repro.launch.serve`` does, with the
full config in bf16 (the published dtype) and seeded random weights, then
checks the kernel path at full width and two layers, where bf16 rounding
stays small.  Train runs the region-captured step (``train/region_step.py``)
at published widths with depth cut to one layer.  Timings are a smoke, not a benchmark.  The
last line of standard output is the JSON result; it is printed only when
every check passed on a TPU.  One process holds the chip throughout.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.cache import enable_xla_disk_cache, xla_cache_dir  # noqa: E402
from repro.configs.qwen2_5_3b import CONFIG  # noqa: E402
from repro.core import tapir  # noqa: E402
from repro.core.lowering import emit  # noqa: E402
from repro.core.schedule import cost_model_for  # noqa: E402
from repro.core.tapir import TapirConfig, use  # noqa: E402
from repro.models.base import get_model  # noqa: E402
from repro.models.layers import bucket_pow2  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.serve import Request, ServeConfig, ServingEngine  # noqa: E402
from repro.train import TrainConfig, init_state, make_region_train_step  # noqa: E402

#: Distances are max |diff| over max |reference| of one prompt's prefill
#: logits.  At full depth random weights amplify bf16 rounding until both
#: bf16 paths sit ~0.25 from float32, so there the region path (and the
#: mesh) may only be compared with the opaque ``jax.jit`` baseline (one
#: device): at most this many times further from float32.
BF16_ERR_FACTOR = 2.0
#: At this depth, full width, bf16 rounding stays near 1e-2, so the region
#: path's kernels are held to an absolute distance from float32 that a
#: faulty kernel (a lost K block, a wrong tile) cannot meet
SHALLOW_LAYERS = 2
SHALLOW_TOL = 3e-2
#: The four-chip mechanism check: f32 activations and f32 ("highest")
#: matmuls at this depth, where mesh and one device may differ only by
#: float32 summation order
MESH_F32_LAYERS = 4
MESH_F32_TOL = 1e-4

SERVE_CFG = dataclasses.replace(CONFIG, param_dtype="bfloat16")
#: the untied 151936 x 2048 embedding and head are 622M parameters; at
#: 16 B/param (f32 weights, grads, two AdamW moments) two layers would
#: already need ~12.4 GB of the chip's 16 GiB, so depth is cut to one
TRAIN_CFG = dataclasses.replace(CONFIG, n_layers=1)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg: str) -> None:
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


def memory() -> dict:
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def impls_bound() -> dict:
    """{op: {impl: nodes}} over every region program compiled so far."""
    out: dict = {}
    for g in tapir.cached_graphs().values():
        for n in g.nodes.values():
            if n.schedule.impl:
                ops = out.setdefault(n.op, {})
                ops[n.schedule.impl] = ops.get(n.schedule.impl, 0) + 1
    return out


def region_hlo_has_kernel(backend: str) -> bool:
    """True when the region programs that bind a kernel impl, one of each
    name, lower to a Mosaic custom call (the graph's lowering re-emitted
    and compiled from its recorded input shapes)."""
    kernel_impls = {"fused_kernel", "flash_kernel", "kernel"}
    found, seen = False, set()
    for g in tapir.cached_graphs().values():
        if g.name in seen or not any(n.schedule.impl in kernel_impls
                                     for n in g.nodes.values()):
            continue
        seen.add(g.name)
        fn = emit(g, backend)
        sds = {name: jax.ShapeDtypeStruct(g.nodes[nid].ttype.shape,
                                          g.nodes[nid].ttype.dtype)
               for name, nid in g.inputs}
        text = jax.jit(fn).lower(sds).compile().as_text()
        if "tpu_custom_call" not in text:
            return False
        found = True
    return found


def make_requests(vocab: int, n: int, lens: tuple, max_new: int,
                  seed: int) -> list:
    rng = np.random.default_rng(seed)
    plens = rng.integers(lens[0], lens[1] + 1, size=n)
    prompts = [rng.integers(1, vocab, size=int(p)).astype(np.int32)
               for p in plens]
    return [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]


def init_params(model, seed: int):
    # one program, so no float32 temporaries of whole leaves
    return jax.jit(model.init_params)(jax.random.PRNGKey(seed))


def _last_logits(model, params, tokens):
    """The plain ``jax.jit`` baseline: per-op opaque lowering, no regions."""
    with use(TapirConfig(mode="opaque", regions=False)):
        return model.forward(params, {"tokens": tokens})[:, -1]


def _rel_err(a, b) -> float:
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def reference_logits(cfg, model, params, prompt) -> tuple:
    """``prompt``'s last logits from the opaque ``jax.jit`` baseline in
    ``cfg``'s dtypes, and from a float32 reference: the same weights, f32
    activations, f32 matmuls."""
    probe = jnp.asarray(prompt[None])
    base = np.asarray(jax.jit(partial(_last_logits, model))(params, probe),
                      np.float32)[0]
    ref_model = get_model(dataclasses.replace(cfg, compute_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(partial(_last_logits, ref_model))(
            params, probe), np.float32)[0]
    return base, ref


def region_prefill_logits(eng, prompt) -> np.ndarray:
    """``prompt``'s prefill logits through the region programs ``eng``
    serves with (its slot params, mesh and config), into a fresh cache."""
    padded = np.zeros((1, min(bucket_pow2(len(prompt)), eng.max_len)),
                      np.int32)
    padded[0, :len(prompt)] = prompt
    with eng._mesh_ctx(), use(eng.cfg.tapir_config()):
        logits, _ = eng.model.prefill_into_slot(
            eng._sp, jnp.asarray(padded), eng._init_slot_cache(), 0,
            len(prompt))
    return np.asarray(logits, np.float32)[0]


def serve_phase(cfg, *, slots: int, max_len: int, n_req: int, lens: tuple,
                max_new: int, seed: int) -> dict:
    """Slot serving through ``ServingEngine.run``; the region path's
    prefill logits are checked against the opaque ``jax.jit`` baseline on
    the same weights, both measured from a float32 reference."""
    model = get_model(cfg)
    t0 = time.perf_counter()
    params = init_params(model, seed)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0

    reqs = make_requests(cfg.vocab, n_req, lens, max_new, seed)
    base, ref = reference_logits(cfg, model, params, reqs[0].prompt)

    scfg = ServeConfig(mode="tapir")
    eng = ServingEngine(model, params, batch=slots, max_len=max_len, cfg=scfg)
    del params      # the engine's slot slices become the only weights

    t0 = time.perf_counter()
    first = [list(r.out) for r in eng.run(reqs)]
    first_s = time.perf_counter() - t0
    tokens = sum(len(o) for o in first)
    reqs2 = make_requests(cfg.vocab, n_req, lens, max_new, seed)
    t0 = time.perf_counter()
    second = [list(r.out) for r in eng.run(reqs2)]
    steady_s = time.perf_counter() - t0

    check(all(len(o) == max_new for o in first), "a request came up short")
    check(all(0 <= t < cfg.vocab for o in first for t in o),
          "token out of range")
    check(first == second, "greedy decode is not deterministic across runs")

    region = region_prefill_logits(eng, reqs[0].prompt)
    check(all(np.isfinite(a).all() for a in (region, base, ref)),
          "non-finite logits")
    check(int(region.argmax()) == first[0][0],
          "region prefill disagrees with the engine's first token")
    err = {"region_vs_f32": _rel_err(region, ref),
           "opaque_vs_f32": _rel_err(base, ref),
           "region_vs_opaque": _rel_err(region, base)}
    check(err["region_vs_f32"] <= BF16_ERR_FACTOR * err["opaque_vs_f32"],
          f"region prefill logits are further from float32 than "
          f"{BF16_ERR_FACTOR}x the opaque baseline's: {err}")
    return {"init_s": init_s, "first_run_s": first_s,
            "steady_run_s": steady_s,
            "compile_s_estimate": first_s - steady_s,
            "tokens_per_run": tokens, "logits_err": err}


def shallow_phase(cfg, *, n_layers: int, slots: int, max_len: int,
                  lens: tuple, seed: int) -> dict:
    """The kernel path held to float32 where bf16 rounding stays small:
    ``cfg`` cut to ``n_layers`` layers, one request through
    ``ServingEngine.run``, then its region prefill logits against the
    float32 reference (limit ``SHALLOW_TOL``)."""
    cfg = dataclasses.replace(cfg, n_layers=n_layers)
    model = get_model(cfg)
    params = init_params(model, seed)
    reqs = make_requests(cfg.vocab, 1, lens, 1, seed)
    base, ref = reference_logits(cfg, model, params, reqs[0].prompt)
    eng = ServingEngine(model, params, batch=slots, max_len=max_len,
                        cfg=ServeConfig(mode="tapir"))
    del params
    out = eng.run(reqs)
    region = region_prefill_logits(eng, reqs[0].prompt)
    check(all(np.isfinite(a).all() for a in (region, base, ref)),
          "non-finite logits")
    check(int(region.argmax()) == out[0].out[0],
          "region prefill disagrees with the engine's token")
    err = {"region_vs_f32": _rel_err(region, ref),
           "opaque_vs_f32": _rel_err(base, ref),
           "region_vs_opaque": _rel_err(region, base)}
    check(err["region_vs_f32"] <= SHALLOW_TOL,
          f"region prefill logits at {n_layers} layers are further than "
          f"{SHALLOW_TOL} from float32: {err}")
    return {"layers": n_layers, "logits_err": err}


def train_phase(cfg, *, seq: int, steps: int, seed: int) -> dict:
    """``steps`` steps of the region-captured train step (f32 weights and
    AdamW state, batch 1); the loss must stay finite."""
    model = get_model(cfg)
    opt_cfg = AdamWConfig(lr=1e-4, warmup_steps=1, total_steps=steps)
    step, _ = make_region_train_step(
        model, opt_cfg, cfg=TrainConfig(mode="tapir", strategy="tp",
                                        remat="auto"))
    state = init_state(model, opt_cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    toks = rng.integers(1, cfg.vocab, size=(1, seq + 1)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:])}
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))   # waits for the step
        times.append(time.perf_counter() - t0)
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    return {"losses": losses, "first_step_s": times[0],
            "steady_step_s": float(np.mean(times[1:])) if steps > 1
            else None, "tokens_per_step": seq}


def mesh_phase(cfg, *, model_axis: int, slots: int, max_len: int,
               n_req: int, lens: tuple, max_new: int, seed: int) -> dict:
    """The same model served on a (data=1, model=``model_axis``) mesh and on
    one device: greedy tokens and one prompt's prefill logits of each,
    compared with each other and with a float32 reference.  Every
    column-TP weight must be split across the mesh's devices.  The one
    device runs under a 1x1 mesh, so both sides bind the same impls (no
    Mosaic kernel under a mesh); the kernel path on one chip is the
    default run's."""
    from repro.launch.mesh import make_test_mesh
    model = get_model(cfg)
    probe = make_requests(cfg.vocab, n_req, lens, max_new, seed)[0].prompt
    _, ref = reference_logits(cfg, model, init_params(model, seed), probe)
    runs, shards = {}, {}
    # the mesh engine first: its params are sliced on device 0 and then
    # spread over the mesh, so the one-device engine can follow without
    # both engines' weights sharing device 0
    for name, m in (("mesh", make_test_mesh(data=1, model=model_axis)),
                    ("one_device", make_test_mesh(data=1, model=1))):
        eng = ServingEngine(model, init_params(model, seed), mesh=m,
                            batch=slots, max_len=max_len,
                            cfg=ServeConfig(mode="tapir"))
        t0 = time.perf_counter()
        out = eng.run(make_requests(cfg.vocab, n_req, lens, max_new, seed))
        runs[name] = {"tokens": [list(r.out) for r in out],
                      "first_run_s": time.perf_counter() - t0,
                      "logits": region_prefill_logits(eng, out[0].prompt)}
        if m.size > 1:
            layer0 = eng._sp["layers"][0][1]
            for leaf, arr in (("wq", layer0["wq"]), ("wg", layer0["wg"]),
                              ("head", eng._sp["head"]["w"])):
                per = {s.device.id: s.data.shape
                       for s in arr.addressable_shards}
                check(len(per) == model_axis and all(
                    shape[-1] * model_axis == arr.shape[-1]
                    for shape in per.values()),
                    f"{leaf} is not split over the mesh: {per}")
                shards[leaf] = {"global": list(arr.shape),
                                "per_device": {str(d): list(s)
                                               for d, s in per.items()}}
        del eng, out
        tapir.clear_cache()
    a, b = runs["mesh"]["tokens"], runs["one_device"]["tokens"]
    first_diff = [next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y),
                       None) for ta, tb in zip(a, b)]
    err = {"mesh_vs_f32": _rel_err(runs["mesh"]["logits"], ref),
           "one_device_vs_f32": _rel_err(runs["one_device"]["logits"], ref),
           "mesh_vs_one_device": _rel_err(runs["mesh"]["logits"],
                                          runs["one_device"]["logits"])}
    check(all(np.isfinite(r["logits"]).all() for r in runs.values()),
          "non-finite logits")
    return {"tokens_identical": a == b,
            "requests_identical": sum(d is None for d in first_diff),
            "first_divergent_token": first_diff, "logits_err": err,
            "shards": shards,
            "first_run_s": {k: v["first_run_s"] for k, v in runs.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 2
    enable_xla_disk_cache()
    cm = cost_model_for()
    log(f"device_kind={dev.device_kind!r} devices={len(jax.devices())} "
        f"cost_model={cm.name} jax={jax.__version__} "
        f"compile_cache={xla_cache_dir()}")
    serve_sizes = dict(slots=4, max_len=2048, n_req=8, lens=(100, 1000),
                       max_new=32, seed=args.seed)

    if args.chips == 4:
        log(f"[mesh] config={SERVE_CFG.name} layers={SERVE_CFG.n_layers} "
            f"param_dtype=bfloat16 mesh=(data=1, model=4) {serve_sizes}")
        bf16 = mesh_phase(SERVE_CFG, model_axis=4, **serve_sizes)
        log("[mesh] " + json.dumps(bf16))
        f32_cfg = dataclasses.replace(SERVE_CFG, n_layers=MESH_F32_LAYERS,
                                      compute_dtype="float32")
        log(f"[mesh-f32] layers={MESH_F32_LAYERS} (cut from "
            f"{CONFIG.n_layers}) compute_dtype=float32 "
            f"matmul_precision=highest")
        with jax.default_matmul_precision("highest"):
            f32 = mesh_phase(f32_cfg, model_axis=4, **serve_sizes)
        log("[mesh-f32] " + json.dumps(f32))
        check(f32["tokens_identical"],
              f"f32 mesh greedy tokens differ from one device: "
              f"{f32['first_divergent_token']}")
        check(f32["logits_err"]["mesh_vs_one_device"] <= MESH_F32_TOL,
              f"f32 mesh logits beyond float32 summation order: "
              f"{f32['logits_err']}")
        err = bf16["logits_err"]
        check(err["mesh_vs_f32"] <= BF16_ERR_FACTOR * err["one_device_vs_f32"],
              f"bf16 mesh prefill logits are further from float32 than "
              f"{BF16_ERR_FACTOR}x one device's: {err}")
    else:
        log(f"[serve] config={SERVE_CFG.name} layers={SERVE_CFG.n_layers} "
            f"d_model={SERVE_CFG.d_model} param_dtype=bfloat16 "
            f"{serve_sizes}")
        res = serve_phase(SERVE_CFG, **serve_sizes)
        log("[serve] smoke, not a benchmark: " + json.dumps(res))
        log(f"[serve] memory={json.dumps(memory())}")
        res = shallow_phase(SERVE_CFG, n_layers=SHALLOW_LAYERS,
                            slots=serve_sizes["slots"],
                            max_len=serve_sizes["max_len"],
                            lens=serve_sizes["lens"], seed=args.seed)
        log(f"[serve] layers={SHALLOW_LAYERS} (cut from {CONFIG.n_layers}) "
            f"vs float32, limit {SHALLOW_TOL}: {json.dumps(res)}")
        check(region_hlo_has_kernel(tapir.get_config().resolved_backend()),
              "no tpu_custom_call in the region programs")
        log(f"[serve] impls={json.dumps(impls_bound())} "
            f"cache_stats={json.dumps(tapir.cache_stats())}")
        tapir.clear_cache()

        log(f"[train] config={TRAIN_CFG.name} layers=1 (cut from "
            f"{CONFIG.n_layers}) d_model={TRAIN_CFG.d_model} "
            f"param_dtype=float32 adamw=float32 batch=1 seq=512 steps=3")
        res = train_phase(TRAIN_CFG, seq=512, steps=3, seed=args.seed)
        log("[train] smoke, not a benchmark: " + json.dumps(res))
        log(f"[train] memory={json.dumps(memory())}")
        log(f"[train] impls={json.dumps(impls_bound())} "
            f"cache_stats={json.dumps(tapir.cache_stats())}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
