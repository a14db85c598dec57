"""95th percentile (nearest rank) of every gap between consecutive output
tokens of one request, over all requests, where the later token came
inside the window."""

from harness.common import nearest_rank


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    w = ctx["window_s"]
    gaps = [b - a for r in ctx["requests"]
            for a, b in zip(r["times"], r["times"][1:]) if b <= w]
    v = nearest_rank(gaps, 95)
    return None if v is None else 1000.0 * v
