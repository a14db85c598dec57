"""Record a small profiler trace on the chip for the reduction's test: two
forward+VJP calls of a small block-sparse head inside a ``bench.window``
span, each issued under ``bench.call``.

    python3 perfbench/tools/record_trace.py <out.xplane.pb>
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import common, counters, trace  # noqa: E402
from harness.sparse_head import block_csr, head_blocks  # noqa: E402
from repro.core.csr import BlockCSR  # noqa: E402
from repro.serve import SparseLogitHead  # noqa: E402


def main(out: str) -> int:
    common.require_devices(1)
    spec = {"head": {"block": 128, "density": 0.5, "pattern_seed": 3}}
    mask = counters.head_mask(3, 1024, 512, 128, 0.5)
    w = block_csr(mask, head_blocks(spec, mask, jax.random.PRNGKey(0)), 128)
    plan = SparseLogitHead.build(w, trainable=True).plan
    meta = (w.block_col, w.block_row, w.row_ptr, w.shape, w.block_shape)

    @jax.jit
    def call(blocks, h, dc):
        out, vjp = jax.vjp(lambda b, x: SparseLogitHead(
            BlockCSR(b, *meta), plan)(x), blocks, h)
        return (out,) + vjp(dc)

    h = jnp.ones((1, 256, 512), jnp.bfloat16)
    dc = jnp.ones((1, 256, 1024), jnp.bfloat16)
    jax.block_until_ready(call(w.blocks, h, dc))
    d = tempfile.mkdtemp()
    trace.start(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.call"):
                r = call(w.blocks, h, dc)
            jax.block_until_ready(r)
    shutil.copy(trace.stop_and_find(d), out)
    print(trace.summary(trace.load(out)))
    print(trace.reduce(trace.load(out)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
