"""Run one cell traced, as ``bench/run.py --trace 1`` does, and read the
program's own spans from the same trace: the device's idle time in the
traced window split by the program phase the host was in, and the span
metrics (``bench.harness.program``).

    python3 bench/phase_split.py --workload <cell> --seed <n> --seconds <s>

Prints the run's result line, then one JSON line: ``window_s``,
``idle_s``, ``phases`` (seconds of device idle time under each innermost
``repro.`` span, largest first), ``engine_idle_share``, ``admit_host_ms``,
``decode_dispatch_ms`` and ``window_captures``.  With no TPU it exits
non-zero and prints nothing.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
from bench import run as bench_run  # noqa: E402  (starts the set-up clock)
from bench.harness import common, program, spec, xplane  # noqa: E402


def run_split(cell, seed: int, seconds: float, device: dict,
              **faults) -> tuple:
    """``bench_run.run_cell`` traced, with the program's spans read from
    the trace file the harness reads.  Returns ``(result, checks,
    report)``."""
    got = {}
    load = xplane.load

    def load_both(path):
        got["spans"] = program.load(path)
        got["trace"] = load(path)
        return got["trace"]

    xplane.load = load_both
    try:
        result, checks = bench_run.run_cell(cell, seed, seconds, True,
                                            device, **faults)
    finally:
        xplane.load = load
    return result, checks, program.report(got["trace"], got["spans"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    common.configure_caches()
    try:
        device = common.require_chips(cell.chips)
    except common.NoChip as e:
        common.log(f"bench/phase_split.py: {e}")
        return bench_run.EXIT_NO_CHIP
    result, checks, report = run_split(cell, args.seed, args.seconds, device)
    common.emit_result(result, checks)
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
