"""Least time the chip needs for the traced decode steps of a
``granitemoehybrid`` configuration over the device busy time inside them,
%.

For each decode step: the held weights read once, each live request's
state rows read and written, its cached keys and values read and one
position written, and the step's operations
(``bench.harness.work_hybrid.decode_step_work``); its least time is the
larger of bytes over peak bandwidth and operations over peak rate.  Busy
time is the union of device operations inside the ``bench.decode`` host
spans of the traced part of the window.  Counts no kernel by name; a
configuration without state rows finds nothing to read."""
import numpy as np

from bench.harness import work_hybrid, xplane


def read(ctx):
    if ctx["kind"] != "serve" or ctx["trace"] is None or "mH" not in ctx["D"]:
        return None
    w, D, pk = ctx["window"], ctx["D"], ctx["peaks"]
    steps = [c for c in w.calls if c.kind == "decode"
             and w.t_open <= c.t0 and c.t1 <= w.t_trace_end]
    lo, hi = xplane.window(ctx["trace"])
    spans = [s for s in xplane.spans_named(ctx["trace"], "bench.decode")
             if s[0] >= lo and s[1] <= hi]
    if not steps or len(spans) != len(steps):
        return None
    prog = ctx["conf"]["program"]
    wb = np.dtype(prog["param_dtype"]).itemsize
    ab = np.dtype(prog["compute_dtype"]).itemsize
    weights = work_hybrid.held_weight_bytes(D, wb)
    least = 0.0
    for c in steps:
        flops, nbytes = work_hybrid.decode_step_work(D, c.keys, weights, ab,
                                                     ab)
        least += max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    busy = xplane.busy_ns(ctx["trace"], spans) / 1e9
    return 100.0 * least / busy if busy > 0 else None
