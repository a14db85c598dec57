"""Plan layer (``kernels/schedule.py``): useful block steps over scheduled
lane steps of the head's train plan, forward and transpose side, in
percent.  A count, not a speed."""


def read(ctx):
    if ctx.get("kind") != "head_vjp":
        return None
    return ctx["plan_utilization"]
