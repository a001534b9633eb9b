"""Serving driver: batched greedy generation with the ServingEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_5_3b --smoke \
        --requests 8 --prompt-len 32 --max-new 16

Fault-tolerance flags exercise the recovery loop: ``--ckpt-dir`` +
``--ckpt-every`` checkpoint slot state periodically; ``--inject-crash``
kills the decode step at that index once (restore + replay);
``--inject-straggle`` delays steps so the watchdog sheds admission.
Outputs stay bitwise identical to an un-faulted run either way.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.dist.fault import Fault, ScriptedFaultInjector
from repro.models.base import get_model
from repro.serve import Request, ServeConfig, ServingEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2_5_3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--mode", default="tapir", choices=["tapir", "opaque"])
    ap.add_argument("--ckpt-dir", default=None,
                    help="slot-state checkpoint directory (enables restore)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="decode steps between periodic slot checkpoints")
    ap.add_argument("--inject-crash", type=int, default=None, metavar="STEP",
                    help="fail the decode step at this index once")
    ap.add_argument("--inject-straggle", type=int, default=None,
                    metavar="STEP", help="start straggling at this step")
    ap.add_argument("--straggle-delay", type=float, default=0.05)
    ap.add_argument("--straggle-repeat", type=int, default=8)
    ap.add_argument("--program-cache-dir", default=None,
                    help="persistent compiled-program store (L2); a warm "
                         "dir makes restarts compile zero XLA programs")
    ap.add_argument("--cache-mode", default="readwrite",
                    choices=["off", "read", "readwrite"])
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="tokens of system-prompt prefix shared by every "
                         "request (0 = fully distinct prompts); resident "
                         "prefix pages make later admits prefill only "
                         "their suffix")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable the shared-prefix page index (baseline)")
    ap.add_argument("--priorities", default=None,
                    help="comma-separated per-request priorities 0..9 "
                         "(cycled); higher may preempt lower when slots "
                         "are full")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request SLO deadline (seconds from start); "
                         "implies --admit-policy slo")
    ap.add_argument("--admit-policy", default=None,
                    choices=["strict", "reject", "slo"])
    args = ap.parse_args(argv)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    prios = ([int(p) for p in args.priorities.split(",")]
             if args.priorities else [0])
    prefix = rng.integers(1, cfg.vocab,
                          size=args.prefix_len).astype(np.int32)
    suffix_len = max(1, args.prompt_len - args.prefix_len)
    reqs = [Request(rid=i,
                    prompt=np.concatenate(
                        [prefix,
                         rng.integers(1, cfg.vocab, size=suffix_len)
                         .astype(np.int32)]),
                    max_new=args.max_new,
                    priority=prios[i % len(prios)],
                    deadline_s=args.deadline_s)
            for i in range(args.requests)]

    faults = {}
    if args.inject_crash is not None:
        faults[args.inject_crash] = Fault("crash")
    if args.inject_straggle is not None:
        faults[args.inject_straggle] = Fault("straggle",
                                             delay_s=args.straggle_delay)
    injector = ScriptedFaultInjector(faults, repeat=args.straggle_repeat) \
        if faults else None

    admit = args.admit_policy or ("slo" if args.deadline_s else "strict")
    eng = ServingEngine(model, params, batch=args.batch,
                        max_len=args.max_len,
                        cfg=ServeConfig(mode=args.mode,
                                        fault_injector=injector,
                                        admit_policy=admit,
                                        prefix_sharing=not args.no_prefix_sharing,
                                        ckpt_dir=args.ckpt_dir,
                                        ckpt_every=args.ckpt_every,
                                        program_cache_dir=args.program_cache_dir,
                                        cache_mode=args.cache_mode))
    t0 = time.time()
    out = eng.run(reqs)
    dt = time.time() - t0
    total_new = sum(len(r.out) for r in out)
    st = eng.last_stats
    report = {
        "requests": len(out),
        "new_tokens": total_new,
        "tok_per_s": total_new / max(dt, 1e-9),
        "sample_out": out[0].out[:8],
        # per-request latency + page-policy observability
        "ttft_p50_ms": round(st.get("ttft_p50", 0.0) * 1e3, 3),
        "ttft_p95_ms": round(st.get("ttft_p95", 0.0) * 1e3, 3),
        "itl_p50_ms": round(st.get("itl_p50", 0.0) * 1e3, 3),
        "itl_p95_ms": round(st.get("itl_p95", 0.0) * 1e3, 3),
        "queue_wait_p50_ms": round(st.get("queue_wait_p50", 0.0) * 1e3, 3),
        "queue_wait_p95_ms": round(st.get("queue_wait_p95", 0.0) * 1e3, 3),
        "prefix_hits": st.get("prefix_hits", 0),
        "prefix_tokens_saved": st.get("prefix_tokens_saved", 0),
        "preemptions": st.get("preemptions", 0),
        "rejected": st.get("rejected", 0),
    }
    if args.program_cache_dir:
        report["cache"] = {k: st.get(k, 0) for k in
                           ("compiled_programs", "l2_hits", "l2_misses",
                            "l2_quarantined", "l2_writes")}
    if injector is not None or args.ckpt_dir:
        report["fault"] = {k: st.get(k, 0) for k in
                           ("failures", "restores", "checkpoints",
                            "shed_rounds", "straggler_steps")}
        report["fault"]["l2_quarantined"] = st.get("l2_quarantined", 0)
        report["step_p95_ms"] = round(st.get("step_p95", 0.0) * 1e3, 3)
    print(json.dumps(report))
    return out


if __name__ == "__main__":
    main()
