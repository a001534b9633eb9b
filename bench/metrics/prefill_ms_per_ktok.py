"""Time in ``prefill_into_slot`` over the prompt kilotokens it admitted in
the traced part of the window, ms per 1000 prompt tokens (host clock)."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    w = ctx["window"]
    pre = [c for c in w.calls if c.kind == "prefill" and w.in_traced(c.t1)]
    toks = sum(c.keys[0] for c in pre)
    if not toks:
        return None
    return 1e3 * sum(c.t1 - c.t0 for c in pre) / (toks / 1e3)
