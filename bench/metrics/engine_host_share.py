"""Share of the traced part of the serving window spent outside the
model's two slot entry points (``prefill_into_slot``,
``decode_step_slots``), %: the engine's own host work (admission, page
books, argmax read-back, feed)."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    w = ctx["window"]
    inside = sum(max(0.0, min(c.t1, w.t_trace_end) - max(c.t0, w.t_open))
                 for c in w.calls)
    return 100.0 * (1.0 - inside / (w.t_trace_end - w.t_open))
