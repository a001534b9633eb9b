"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``.  The run draws
its weights and traffic from ``--seed``, warms up every program shape the
cell's traffic reaches, measures for ``--seconds``, checks what the timed
path produced against the float32 reference, and prints one JSON line as
the last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device`` and, last, ``checks``
(each compared number beside its limit, also the last lines of standard
error).  With no TPU, or fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import common, spec  # noqa: E402

#: exit code for "no chip"; any other failure raises (exit code 1)
EXIT_NO_CHIP = 3


def metrics_of(entries: list, ctx: dict) -> dict:
    out = {}
    for m in entries:
        value = spec.metric_reader(m["name"]).read(ctx)
        if value is None:
            common.log(f"metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def trace_fields(ctx: dict) -> tuple:
    """``(busy_s, window_s, breakdown)`` from the traced window."""
    from bench.harness import xplane
    tr = ctx["trace"]
    lo, hi = xplane.window(tr)
    busy = xplane.busy_ns(tr, [(lo, hi)]) / 1e9
    return busy, (hi - lo) / 1e9, {
        "device_ops": xplane.top_ops(tr, lo, hi),
        "idle_gaps": xplane.idle_gaps(tr, lo, hi)}


def run_cell(cell, seed: int, seconds: float, trace: bool,
             device: dict, **faults) -> tuple:
    """The harness of the cell's traffic kind, then its metrics.  Returns
    ``(result, checks)``."""
    result, checks, ctx = spec.harness(cell.traffic).run(
        cell, seed, seconds, trace, T_START, device, **faults)
    result["metrics"] = metrics_of(cell.per_layer if trace
                                   else cell.end_to_end, ctx)
    if trace:
        busy, window, breakdown = trace_fields(ctx)
        result["device"] = dict(result["device"], busy_s=busy,
                                window_s=window)
        result["breakdown"] = breakdown
    common.log(f"set-up {ctx['setup_s']:.3f} s, compiles in window "
               f"{ctx['window_compiles']}")
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    cell = spec.load_cell(args.workload)
    common.configure_caches()
    try:
        device = common.require_chips(cell.chips)
    except common.NoChip as e:
        common.log(f"bench/run.py: {e}")
        return EXIT_NO_CHIP
    result, checks = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), device)
    common.emit_result(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
