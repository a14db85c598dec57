"""Prefill at admission: device seconds of the ``jit_prefill`` and
``jit_head_prefill`` programs in the window over the admissions the
program made there (its ``serve.admit`` spans), in ms
(``harness/program_trace.py``)."""

from harness import program_trace


def read(ctx):
    r = program_trace.reading(ctx)
    if not r:
        return None
    admits = r["spans"].get("serve.admit", {}).get("n")
    mods = r["module_s"]
    if not admits or "jit_prefill" not in mods:
        return None
    return 1000.0 * (mods["jit_prefill"]
                     + mods.get("jit_head_prefill", 0.0)) / admits
