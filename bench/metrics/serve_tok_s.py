"""Output tokens produced in the serving window over the window's length,
tokens/s (host clock).  Every token counts: the first of a request (its
prefill) and each decode step's."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    w = ctx["window"]
    n = sum(1 for tt in w.token_times for t in tt if w.in_window(t))
    return n / (w.t_close - w.t_open)
