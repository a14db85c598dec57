"""Serving layer (batcher admission): 95th percentile (nearest rank) of
``t_admit - due`` over every request due in the window; one never
admitted counts as waiting until the run stopped waiting for it."""

from harness.common import nearest_rank


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    vals = [(r["t_admit"] if r["t_admit"] is not None else ctx["cap_s"])
            - r["due"] for r in ctx["requests"]]
    return 1000.0 * nearest_rank(vals, 95)
