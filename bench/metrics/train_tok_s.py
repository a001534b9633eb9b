"""Tokens of all training steps completed in the window over the window's
length, tokens/s (host clock)."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    t_open, t_close = ctx["window"]
    return ctx["steps"] * ctx["tokens_per_step"] / (t_close - t_open)
