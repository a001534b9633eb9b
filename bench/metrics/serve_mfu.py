"""Model operations of every prompt and output token served in the traced
part of the window over its length and the chip's bf16 peak, %.  A
prompt counts its forward pass with causal attention and the head for its
last position; an output token counts its pass through every layer, its
attention over its cached positions, and the head."""
from bench.harness import work


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    w, D = ctx["window"], ctx["D"]
    flops = 0.0
    for c in w.calls:
        if not w.in_traced(c.t1):
            continue
        if c.kind == "prefill":
            flops += work.prefill_flops(D, c.keys[0])
        else:
            flops += sum(work.decode_token_flops(D, k) for k in c.keys)
    return (100.0 * flops / (w.t_trace_end - w.t_open)
            / ctx["peaks"]["bf16_flops"])
