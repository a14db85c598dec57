"""The window divided by the calls completed in it; calls are issued back
to back and the window ends at ``block_until_ready`` of the last one."""


def read(ctx):
    if ctx.get("kind") != "head_vjp":
        return None
    return 1000.0 * ctx["window_s"] / ctx["calls"]
