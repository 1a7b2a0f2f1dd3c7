"""Whole-step model FLOP/s utilization, in %: the model FLOPs per trained
token times the tokens per second of the traced run, over chips times
the bf16 peak.  The master's update FLOPs are not counted."""


def flops_per_token(arch, seq: int) -> float:
    """6 x the matrix-product parameters (the embedding gather is not a
    product) plus attention's 12 x layers x heads x head_dim x seq:
    scores and values, forward and backward, over the whole sequence."""
    d, h, kv, hd, f = (arch.d_model, arch.num_heads, arch.num_kv_heads,
                       arch.head_dim, arch.d_ff)
    per_layer = 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f
    matmul = arch.num_layers * per_layer + d * arch.vocab_size
    return 6.0 * matmul + 12.0 * arch.num_layers * h * hd * seq


def read(ctx):
    if ctx.get("trace") is None or not ctx.get("window_s") \
            or ctx.get("peaks") is None:
        return None
    rate = ctx["tokens"] / ctx["window_s"]
    flops = flops_per_token(ctx["arch"], ctx["traffic"]["seq"]) * rate
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
