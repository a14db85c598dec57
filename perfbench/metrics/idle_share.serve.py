"""Device: 1 - busy / window from the profiler trace of the window, in
percent (busy = union of the device's op intervals, averaged over chips)."""


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("kind") != "serve" or not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
