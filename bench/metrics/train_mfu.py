"""Model operations per token (6 per matrix weight, head included, plus
causal attention forward and backward; recomputation not counted) times
the tokens per second of the window, over the chip's bf16 peak, %."""
from bench.harness import work


def read(ctx):
    if ctx["kind"] != "train":
        return None
    t_open, t_close = ctx["window"]
    tok_s = ctx["steps"] * ctx["tokens_per_step"] / (t_close - t_open)
    per_tok = work.train_token_flops(ctx["D"], int(ctx["job"]["seq"]))
    return 100.0 * per_tok * tok_s / ctx["peaks"]["bf16_flops"]
