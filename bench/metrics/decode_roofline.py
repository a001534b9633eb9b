"""Least time the chip needs for the traced decode steps over the device
busy time inside them, %.

For each decode step: every weight read once, each live request's cached
keys and values read and one position written, and the step's operations
(``bench.harness.work.decode_step_work``); its least time is the larger of
bytes over peak bandwidth and operations over peak rate.  Busy time is the
union of device operations inside the ``bench.decode`` host spans of the
traced part of the window (the observer blocks on each step's logits, so
a step's device work ends inside its span).  Counts no kernel by name."""
import numpy as np

from bench.harness import work, xplane


def read(ctx):
    if ctx["kind"] != "serve" or ctx["trace"] is None:
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
    kvb = np.dtype(prog["compute_dtype"]).itemsize
    least = 0.0
    for c in steps:
        flops, nbytes = work.decode_step_work(
            D, c.keys, work.decode_weight_bytes(D, wb), kvb)
        least += max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    busy = xplane.busy_ns(ctx["trace"], spans) / 1e9
    return 100.0 * least / busy if busy > 0 else None
