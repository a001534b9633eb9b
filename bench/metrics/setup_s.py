"""Process start to window open, s (host clock): imports, weights drawn on
the device, compiling or loading every program, warm-up, and for serving
the first fill of every slot."""


def read(ctx):
    return ctx["setup_s"]
