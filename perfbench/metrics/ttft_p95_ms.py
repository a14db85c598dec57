"""95th percentile (nearest rank) of time to first token over every
request due in the window, timed from when it was due (open loop).  A
request that never got a first token counts as waiting until the run
stopped waiting for it."""

from harness.common import nearest_rank


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    vals = [(r["times"][0] if r["times"] else ctx["cap_s"]) - r["due"]
            for r in ctx["requests"]]
    return 1000.0 * nearest_rank(vals, 95)
