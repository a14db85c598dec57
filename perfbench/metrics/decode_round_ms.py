"""Model step (fused decode + head + sampling): host-clock mean of the
``ContinuousBatcher.step`` rounds that decoded, over all such rounds that
opened inside the window."""


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    d = [t1 - t0 for t0, t1, decoded in ctx["rounds"]
         if decoded and t0 < ctx["window_s"]]
    return 1000.0 * sum(d) / len(d) if d else None
