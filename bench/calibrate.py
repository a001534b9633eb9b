"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5 [--control fp8]

One process runs the cell once per seed (compiled programs are reused
across seeds) and prints one JSON line per seed: the numbers ``correct``
compares, for the program and, with ``--control``, for the reference
computed one precision step below the configuration's (``fp8``: see
``bench.reference.qwen2.einsum``) and for the planted faults the harness can
read without the program (training: half of each batch).  Each side is
scored by the harness's own checks against the configuration's limits,
as a run scores the program: every number with its limit and ``ok`` or
``FAILED`` on standard error, and ``correct`` per side in the line.  The
control and each fault have to come out not correct.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import common, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--dump", default=None,
                    help="a JSON lines file for each seed's raw readings "
                    "(training: losses and every tensor's norms)")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    common.configure_caches()
    try:
        device = common.require_chips(cell.chips)
    except common.NoChip as e:
        common.log(f"bench/calibrate.py: {e}")
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        result, checks, ctx = spec.harness(cell.traffic).run(
            cell, seed, args.seconds, False, common.now(), device,
            control=args.control)
        sides = {"program": checks}
        if ctx.get("control") is not None:
            sides["control"] = ctx["score"](ctx["control"])
        for name, numbers in (ctx.get("faults") or {}).items():
            sides[name] = ctx["score"](numbers)
        for side, cs in sides.items():
            for c in cs:
                common.log(f"seed {seed} {side} {c['name']}: {c['value']!r} "
                           f"limit {c['limit']!r} "
                           f"{'ok' if c['ok'] else 'FAILED'}")
        print(json.dumps({
            "seed": seed,
            "correct": {side: all(c["ok"] for c in cs)
                        for side, cs in sides.items()},
            "program": {c["name"]: c["value"] for c in checks},
            "control": ctx.get("control"), "faults": ctx.get("faults"),
            "setup_s": ctx["setup_s"]}), flush=True)
        if args.dump and "raw" in ctx:
            with open(args.dump, "a") as f:
                f.write(json.dumps({"seed": seed, **ctx["raw"]}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
