"""Sampling (the batcher's host path): seconds of the program's
``serve.sample`` spans in the window over its fused steps there (its
``serve.decode`` spans), in ms; the samples of admissions count too
(``harness/program_trace.py``)."""

from harness import program_trace


def read(ctx):
    r = program_trace.reading(ctx)
    if not r:
        return None
    steps = r["spans"].get("serve.decode", {}).get("n")
    if not steps or "serve.sample" not in r["spans"]:
        return None
    return 1000.0 * r["spans"]["serve.sample"]["s"] / steps
