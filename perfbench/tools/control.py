"""Readings that set a cell's limits: the program's number on many seeds
and the fp8 control's on a few, at the cell's own size, in one process.

    python3 perfbench/tools/control.py --workload qwen3-4b.chat \
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds 20

Serving cells run a short window per seed at the cell's own load and
compare its finished requests; the control reads, at each position of the
same prompts and served tokens, the gap of the token that the reference
with fp8 weights puts first.  Head cells compare one call per seed; the
control is the reference computed on fp8-rounded operands.  One JSON line
per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from harness import common, registry  # noqa: E402
from harness import traffic as gen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    a = ap.parse_args(argv)
    cell = registry.load_cell(a.workload)
    common.require_devices(cell.chips)
    from repro.launch import compile_cache
    compile_cache.enable()
    drv = cell.runner()
    ctl = {int(s) for s in a.control_seeds.split(",") if s}
    for seed in (int(s) for s in a.seeds.split(",")):
        line = {"workload": cell.name, "seed": seed}
        if cell.traffic["kind"] == "serve":
            srv = drv.Serve(cell, seed)
            srv.warm(seed)
            reqs = gen.serve_requests(cell.traffic, seed, a.seconds,
                                      srv.cfg.vocab_size)
            out = srv.window(reqs, a.seconds)
            # let every request due finish, so the longest is compared
            for _ in range(100000):
                if srv.engine.idle():
                    break
                srv.engine.step(1e9)
            done = {c.rid: c for c in srv.engine.completions}
            for rec in out["requests"]:
                c = done.get(rec["rid"])
                if c is not None:
                    rec["status"], rec["tokens"] = c.status, list(c.tokens)
            srv.free_program_state()
            res = srv.compare(out, seed,
                              int(cell.traffic["check"]["requests"]),
                              control=seed in ctl)
            line.update(program_gap=max(res["program"]),
                        program_per_request=res["program"],
                        tokens=res["tokens"], requests=res["n"])
            if seed in ctl:
                line.update(control_gap=max(res["control"]),
                            control_per_request=res["control"])
            del srv
        else:
            hv = drv.HeadVJP(cell, seed)
            last = hv.call(hv.blocks, hv.h, hv.dc)
            hv.free_program_state()
            line["program"] = hv.compare(last)
            del last
            gc.collect()
            if seed in ctl:
                line["control"] = hv.compare(None, control=True)
            del hv
        gc.collect()
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
