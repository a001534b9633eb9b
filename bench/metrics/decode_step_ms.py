"""Median time of one ``decode_step_slots`` call in the traced part of the
window, ms (host clock, ended by the step's logits)."""
import numpy as np


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    w = ctx["window"]
    d = [c.t1 - c.t0 for c in w.calls
         if c.kind == "decode" and w.in_traced(c.t1)]
    return float(np.median(d)) * 1e3 if d else None
