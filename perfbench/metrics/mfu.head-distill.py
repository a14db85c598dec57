"""Whole step: the call's useful FLOPs over ``call_ms``, over the chip's
bf16 peak, in percent."""


def read(ctx):
    if ctx.get("kind") != "head_vjp":
        return None
    per_call = ctx["window_s"] / ctx["calls"]
    return 100.0 * ctx["work"]["flops"] / per_call / (
        ctx["peak"]["bf16_flop_per_s"] * ctx["device"]["count"])
