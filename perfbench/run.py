"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix, runner and metric readers are found by name under
``perfbench/``.  Exits non-zero, printing no result, when JAX finds no
accelerator or fewer chips than the cell asks for.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` the
``breakdown``, and last the ``checks``: each number compared with its
limit.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
from harness import common  # noqa: E402  (starts the set-up clock)

sys.path.insert(0, str(HERE.parent / "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="where the profiler writes (default: "
                         "perfbench_out/trace/<workload> in the checkout)")
    return ap.parse_args(argv)


def execute(cell, args, devices):
    """Everything after the look for a chip: set-up, window, readings,
    comparison, result line.  Returns the exit code."""
    import jax
    from harness import peaks as pk
    from harness import registry
    if not args.trace_dir:
        args.trace_dir = str(common.ROOT / "perfbench_out" / "trace"
                             / cell.name)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = common.CompileCounter()
    ctx = cell.runner().run(cell, args, devices, counter)
    ctx["peak"] = pk.peaks(ctx["device"]["kind"])
    ctx["config"] = cell.config
    entries = cell.per_layer if args.trace else cell.end_to_end
    metrics = registry.read_metrics(entries, ctx)
    device = dict(ctx["device"])
    breakdown = None
    if args.trace:
        t = ctx["trace"] or {}
        device["busy_s"] = t.get("busy_s")
        device["window_s"] = t.get("window_s")
        breakdown = t.get("breakdown")
    correct = common.checks_pass(ctx["checks"])
    common.print_result(correct=correct, attempted=ctx["attempted"],
                        failed=ctx["failed"], metrics=metrics,
                        device=device, checks=ctx["checks"],
                        breakdown=breakdown)
    return 0


def main(argv=None) -> int:
    args = parse(argv)
    from harness import registry
    cell = registry.load_cell(args.workload)
    try:
        devices = common.require_devices(cell.chips)
    except common.NoDevice as e:
        common.log(f"run.py: {e}; there is no CPU fallback")
        return 2
    from repro.launch import compile_cache
    compile_cache.enable()
    common.mark("imports and devices")
    return execute(cell, args, devices)


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
