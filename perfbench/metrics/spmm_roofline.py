"""Pallas kernels: the least time the chip could take for one call's
useful work — the larger of useful FLOPs over peak FLOP/s and needed bytes
over HBM bandwidth (``harness.counters.spmm_vjp_work``) — over the device
time of the call's kernels, in percent.  Until every ``pallas_call`` has a
``name=``, the kernels are the trace's custom-call ops in the window."""

from harness.counters import roofline_seconds


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("kind") != "head_vjp" or not t or not t.get("kernel_s"):
        return None
    per_call = t["kernel_s"] / ctx["calls"]
    return 100.0 * roofline_seconds(ctx["work"], ctx["peak"])["seconds"] \
        / per_call
