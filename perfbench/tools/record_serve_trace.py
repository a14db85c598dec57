"""Record a small profiler trace of the serving loop on the chip for the
readers' tests: the smoke-size ``qwen3-4b.chat`` cell of the CPU tests
(two layers, d_model 128, a 1024-row head of 128x128 blocks) serves two
requests of one prompt length through the runner's ``Serve`` with its
outside spans on, every round under ``bench.step`` inside one
``bench.window`` span.  The profiler records no Python calls and fewer
runtime events than by default; ``trim_trace.py`` then cuts the recording
to what the reductions read, for the tests' data:

    python3 perfbench/tools/record_serve_trace.py <raw.xplane.pb>   # chip
    python3 perfbench/tools/trim_trace.py <raw.xplane.pb> \
        perfbench/tests/data/small_serve_trace.xplane.pb
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "tests"))
sys.path.insert(0, str(HERE.parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import smoke  # noqa: E402
from harness import common, program_trace, trace  # noqa: E402
from repro.serve import Request  # noqa: E402

SEED = 5
PROMPTS = (16, 16)
NEW_TOKENS = 3


def record(out: str) -> dict:
    cell = smoke.cell("qwen3-4b.chat")
    cell.config["head"]["block"] = 128       # the chip's tile
    srv = cell.runner().Serve(cell, SEED)
    srv.warm(SEED)
    srv.instrument()
    eng = srv.engine
    rng = np.random.default_rng(SEED)
    reqs = [Request(tokens=rng.integers(0, srv.cfg.vocab_size, n),
                    max_new_tokens=NEW_TOKENS, arrival=0.0) for n in PROMPTS]
    if srv.queue.submit_all(reqs) != len(reqs):
        raise RuntimeError("the queue refused a request")
    d = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        while not eng.idle():
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step(0.0)
        jax.block_until_ready(eng.state)
    shutil.copy(trace.stop_and_find(d), out)
    shutil.rmtree(d, ignore_errors=True)
    return {"rounds": eng.rounds, "steps": eng.steps,
            "admitted": eng.admitted,
            "pages_in_use_sum": eng.pages_in_use_sum}


def main(out: str) -> int:
    common.require_devices(1)
    counters = record(out)
    rec = program_trace.load(out)
    print(trace.summary(rec))
    print(trace.reduce(rec))
    print(program_trace.reduce(rec))
    print(f"counters after warm-up and window {counters}; "
          f"{os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
