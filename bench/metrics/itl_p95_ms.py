"""95th percentile of every gap between two consecutive output tokens of
one request, over all requests, for gaps that end in the window, ms (host
clock).  A prefill admitted between decode steps stalls every running
request, so prefill cost shows here."""
import numpy as np


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    w = ctx["window"]
    gaps = [b - a for tt in w.token_times for a, b in zip(tt, tt[1:])
            if w.in_window(b)]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
