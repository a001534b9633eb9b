"""A training cell: the region-captured step (``make_region_train_step``),
driven from the seed, then a timed window of further steps.

Set-up builds the one compiled step and its state and drives it through
its first three steps, through the same call and batch feed the window
uses, on rows that all differ.  Before the state moves on, it reads what
the comparison needs: each step's loss; the first gradient as AdamW got
it (after clipping), per tensor, from the first moment after step one
(``mu = (1 - b1) g``); and the change of every tensor after three steps,
against the initial weights drawn again from the seed.  After one more
warm step the same object runs the window.  Once the window has closed
and the state is freed, the float32 reference takes the same three steps
on the same rows.
"""
from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import common, spec, traffic

CHECKED_STEPS = 3
#: rows of a batch the reference takes through one forward and backward
#: pass; the batch's loss and gradient are the token-weighted mean of its
#: blocks', so that the reference's activations fit beside its state
REF_ROWS = 2
#: tensors whose reference gradient is below this share of the median
#: tensor's are rounding noise in the reference (a key bias under softmax
#: has an exact gradient of zero); their change is not compared
NOISE_GRAD_SHARE = 1e-3


def _median(d: dict) -> float:
    return float(np.median(list(d.values())))


def leaf_gaps(prog: dict, refv: dict, names=None) -> dict:
    """Each tensor's ``|prog - ref| / max(ref, median ref)``."""
    names = list(refv) if names is None else list(names)
    med = float(np.median([refv[n] for n in names]))
    return {n: abs(prog[n] - refv[n]) / max(refv[n], med) for n in names}


def gap(prog: dict, refv: dict, names=None) -> tuple:
    """Worst tensor by ``leaf_gaps``: ``(gap, name)``."""
    g = leaf_gaps(prog, refv, names)
    worst = max(g, key=g.get)
    return g[worst], worst


def readings(prog: dict, refv: dict) -> dict:
    """The compared numbers for one side against the reference, and
    what the look at them needs: each step's loss gap, and the median
    tensor's gradient and change gaps."""
    steps = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], refv["loss"])]
    gmed = _median(refv["grad"])
    moved = [n for n, v in refv["grad"].items() if v >= NOISE_GRAD_SHARE * gmed]
    gl = leaf_gaps(prog["grad"], refv["grad"])
    cl = leaf_gaps(prog["change"], refv["change"], moved)
    gname, cname = max(gl, key=gl.get), max(cl, key=cl.get)
    return {"loss_gap": max(steps), "loss_gaps": steps,
            "grad_gap": gl[gname], "grad_worst": gname,
            "grad_median": float(np.median(list(gl.values()))),
            "change_gap": cl[cname], "change_worst": cname,
            "change_median": float(np.median(list(cl.values()))),
            "left_out": sorted(set(refv["grad"]) - set(moved))}


def score(numbers: dict, conf: dict) -> list:
    """The checks of ``correct`` over one side's ``readings``, each beside
    its limit: the program's in a run, the control's and the faults' in
    calibration."""
    return [{"name": n, "value": numbers[n], "limit": float(lim),
             "ok": bool(np.isfinite(numbers[n])
                        and numbers[n] <= float(lim))}
            for n, lim in conf["correct"].items()]


def _loss_and_grad(lg, params, toks):
    """The mean loss over every position of ``toks`` and its gradient,
    ``REF_ROWS`` rows at a time."""
    n = len(toks)
    loss, grad = 0.0, None
    for a in range(0, n, REF_ROWS):
        blk = jnp.asarray(toks[a:a + REF_ROWS])
        w = blk.shape[0] / n
        l, g = lg(params, blk[:, :-1], blk[:, 1:])
        loss = loss + w * l
        g = jax.tree_util.tree_map(lambda x: w * x, g)
        grad = g if grad is None else jax.tree_util.tree_map(jnp.add, grad, g)
    return loss, grad


def reference_steps(conf: dict, job: dict, seed: int, mode: str = "f32",
                    rows: int = None) -> dict:
    """The reference's three steps: losses, first clipped gradient norms,
    change norms.  ``rows`` keeps only the first rows of each batch (a
    planted fault)."""
    ad, ref = spec.adapter(conf), spec.reference(conf)
    opt = dict(job["optimizer"])
    V = int(conf["vocab_size"])
    p0 = ad.reference_params(conf, seed)
    params = p0
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
    m, v = zeros, zeros
    lg = jax.jit(jax.value_and_grad(
        lambda p, t, y: ref.loss(conf, p, t, y, mode)))
    out = {"loss": []}
    for s in range(CHECKED_STEPS):
        toks = traffic.train_batch(job, seed, s, V)[:rows]
        loss, g = _loss_and_grad(lg, params, toks)
        params, m, v, gc_ = ref.adamw_step(opt, params, g, m, v, s + 1)
        out["loss"].append(float(loss))
        if s == 0:
            out["grad"] = ad.reference_leaf_norms(gc_)
        del g, gc_
    out["change"] = ad.reference_leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a - b, params, p0))
    return out


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, device: dict, wrap_step=None,
        control: str = None) -> tuple:
    """One run of a training cell.  ``wrap_step`` (tests) wraps the
    compiled step to plant a fault; ``control`` (calibration) also reads
    the reference computed in that lower precision, and the reference fed
    half of each batch, into ``ctx["control"]`` and ``ctx["faults"]``."""
    from repro.models.base import get_model
    from repro.optim import AdamWConfig
    from repro.optim.adamw import adamw_init
    from repro.train import TrainConfig, make_region_train_step
    conf, job = cell.conf, cell.traffic
    ad = spec.adapter(conf)
    D = spec.reference(conf).dims(conf)
    V = D["V"]
    opt_cfg = AdamWConfig(**job["optimizer"])
    model = get_model(ad.model_config(conf, cell.config_name))
    step, _ = make_region_train_step(
        model, opt_cfg, cfg=TrainConfig(mode="tapir", strategy="tp",
                                        remat=job["remat"]))
    if wrap_step is not None:
        step = wrap_step(step)
    counter = common.CompileCounter()

    def batch(k):
        toks = traffic.train_batch(job, seed, k, V)
        return {"tokens": jnp.asarray(toks[:, :-1]),
                "labels": jnp.asarray(toks[:, 1:])}

    params = ad.program_params(conf, seed)
    state = {"params": params, "opt": adamw_init(params, opt_cfg)}
    del params
    prog = {"loss": []}
    for k in range(CHECKED_STEPS):
        state, met = step(state, batch(k))
        prog["loss"].append(float(met["loss"]))
        if k == 0:
            g = ad.program_leaf_norms(state["opt"]["mu"])
            prog["grad"] = {n: x / (1.0 - opt_cfg.b1) for n, x in g.items()}
    p0 = ad.program_params(conf, seed)
    prog["change"] = ad.program_leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, state["params"], p0))
    del p0
    state, met = step(state, batch(CHECKED_STEPS))      # the warm step
    float(met["loss"])
    tokens_per_step = int(job["batch"]) * int(job["seq"])

    gcw = common.GcWatch()
    common.settle_heap()
    with common.profiler_session(trace) as tdir:
        tw = None
        if tdir is not None:
            common.start_trace(tdir)
            tw = jax.profiler.TraceAnnotation("bench.window")
            tw.__enter__()
        counter.active = gcw.active = True
        k, done, pending = CHECKED_STEPS + 1, 0, None
        t_open = common.now()
        synced = [t_open]
        while True:
            with common.span("batch", trace):
                b = batch(k)
            with common.span("step", trace):
                state, met = step(state, b)
            k += 1
            if pending is not None:
                with common.span("sync", trace):
                    float(pending["loss"])
                done += 1
                synced.append(common.now())
            pending = met
            if common.now() - t_open >= seconds:
                with common.span("sync", trace):
                    float(pending["loss"])
                done += 1
                synced.append(common.now())
                break
        t_close = common.now()
        counter.active = gcw.active = False
        in_use = common.memory_in_use_bytes(device["count"])
        trace_data = None
        if tw is not None:
            tw.__exit__(None, None, None)
            jax.profiler.stop_trace()
            from bench.harness import xplane
            t_load = common.now()
            trace_data = xplane.load(xplane.find_xplane(tdir))
            common.log(f"trace read in {common.now() - t_load:.1f} s")
    device = dict(device, memory_peak_bytes=common.memory_peak_bytes(
        device["count"]))
    if counter.count:
        common.log(f"WARNING: {counter.count} programs compiled inside the "
                   f"window")
    gaps = np.diff(synced) * 1e3
    common.log(f"window: {done} steps, ms between results: median "
               f"{np.median(gaps):.2f}, p90 {np.percentile(gaps, 90):.2f}, "
               f"max {gaps.max():.2f}; first third {np.median(gaps[:len(gaps) // 3]):.2f}, "
               f"last third {np.median(gaps[-(len(gaps) // 3):]):.2f}; "
               f"collector: {gcw.close()}; bytes in use at the close "
               f"{in_use}, peak {device['memory_peak_bytes']}")
    del state, met, pending, b, step, model
    gc.collect()

    t_ref = common.now()
    refv = reference_steps(conf, job, seed)
    common.log(f"reference steps took {common.now() - t_ref:.2f} s")
    r = readings(prog, refv)
    common.log("train readings: " + ", ".join(
        f"{k}={v}" for k, v in r.items()))
    checks = score(r, conf)
    ctx = dict(kind="train", D=D, conf=conf, job=job,
               window=(t_open, t_close), steps=done,
               tokens_per_step=tokens_per_step, setup_s=t_open - t_start,
               trace=trace_data, device=device,
               peaks=common.peaks_for(device["kind"]),
               window_compiles=counter.count,
               score=lambda numbers: score(numbers, conf))
    if control is not None:
        ctl = reference_steps(conf, job, seed, control)
        half = reference_steps(conf, job, seed, rows=int(job["batch"]) // 2)
        ctx["control"] = readings(ctl, refv)
        ctx["faults"] = {"half_batch": readings(half, refv)}
        ctx["raw"] = {"program": prog, "reference": refv, "control": ctl,
                      "half_batch": half}
    result = {"correct": all(c["ok"] for c in checks), "attempted": done,
              "failed": 0, "device": device}
    return result, checks, ctx
