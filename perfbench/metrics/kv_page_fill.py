"""Paged KV cache: the pool's pages in use over its usable pages,
averaged over the fused steps of the window, in percent.  Each
``serve.decode`` span carries the allocator's ``pages_in_use`` and
``pages_free`` at that step, so their sums over the window are the
batcher's ``pages_in_use_sum`` counter over the window and the steps
times the pool (``harness/program_trace.py``)."""

from harness import program_trace


def read(ctx):
    r = program_trace.reading(ctx)
    if not r:
        return None
    sums = r["spans"].get("serve.decode", {}).get("sum", {})
    if "pages_in_use" not in sums or "pages_free" not in sums:
        return None
    pool = sums["pages_in_use"] + sums["pages_free"]
    return 100.0 * sums["pages_in_use"] / pool if pool else None
