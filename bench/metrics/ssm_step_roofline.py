"""Roofline share of the one-position SSM state-update kernel, %: the
bytes its calls need (``bench.harness.work_hybrid.ssm_step_bytes``, every
slot's row of one layer per call) at peak HBM bandwidth, over the device
time of the operations named ``ssm_state_step`` in the traced part of the
window (mean over device planes).  Its operations are a few per byte, so
bandwidth bounds it.  A program without that kernel finds nothing to
read."""
from bench.harness import work_hybrid, xplane

KERNEL = "ssm_state_step"


def _is_kernel(name: str) -> bool:
    op = xplane.short_op(name).lstrip("%")
    return op == KERNEL or op.startswith(KERNEL + ".") \
        or op.startswith(KERNEL + " ")


def read(ctx):
    if ctx["kind"] != "serve" or ctx["trace"] is None or "mH" not in ctx["D"]:
        return None
    tr = ctx["trace"]
    lo, hi = xplane.window(tr)
    calls, ns = 0, 0.0
    for ops in tr.ops.values():
        for s, e, name in ops:
            if s >= lo and e <= hi and _is_kernel(name):
                calls += 1
                ns += e - s
    if not calls or ns <= 0:
        return None
    per_call = work_hybrid.ssm_step_bytes(ctx["D"], int(ctx["mix"]["slots"]))
    return 100.0 * (calls * per_call / ctx["peaks"]["hbm_bytes_per_s"]) \
        / (ns / 1e9)
