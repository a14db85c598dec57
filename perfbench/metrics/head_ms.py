"""Sparse head at decode: device seconds of the ``jit_head_decode``
program in the window over the fused steps the program ran there (its
``serve.decode`` spans), in ms (``harness/program_trace.py``)."""

from harness import program_trace


def read(ctx):
    r = program_trace.reading(ctx)
    if not r:
        return None
    steps = r["spans"].get("serve.decode", {}).get("n")
    head = r["module_s"].get("jit_head_decode")
    if not steps or head is None:
        return None
    return 1000.0 * head / steps
