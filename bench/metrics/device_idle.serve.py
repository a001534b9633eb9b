"""Share of the traced serving window in which no operation ran on the
device, %: 1 - busy / window, both from the profiler's trace."""
from bench.harness import xplane


def read(ctx):
    if ctx["kind"] != "serve" or ctx["trace"] is None \
            or not ctx["trace"].ops:
        return None
    lo, hi = xplane.window(ctx["trace"])
    return 100.0 * (1.0 - xplane.busy_ns(ctx["trace"], [(lo, hi)]) / (hi - lo))
