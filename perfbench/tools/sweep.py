"""Find the highest arrival rate a serving cell's batcher sustains: one
process, one set-up, then a window at each rate of a list, drained
between rates.  Prints one JSON line per rate.

    python3 perfbench/tools/sweep.py --workload qwen3-4b.chat \
        --rates 0.4,0.6,0.8 --seconds 30 --seed 1
"""

from __future__ import annotations

import argparse
import copy
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    cell = registry.load_cell(a.workload)
    common.require_devices(cell.chips)
    from repro.launch import compile_cache
    compile_cache.enable()
    drv = cell.runner()
    srv = drv.Serve(cell, a.seed)
    srv.warm(a.seed)
    for i, rate in enumerate(float(r) for r in a.rates.split(",")):
        tr = copy.deepcopy(cell.traffic)
        tr["arrival"]["rate_per_s"] = rate
        reqs = gen.serve_requests(tr, a.seed + i, a.seconds,
                                  srv.cfg.vocab_size)
        out = srv.window(reqs, a.seconds)
        w = out["window_s"]
        ttft = sorted(r["times"][0] - r["due"] for r in out["requests"]
                      if r["times"])
        backlog = sum(1 for r in out["requests"]
                      if r["t_admit"] is None or r["t_admit"] > w)
        late_wait = [r["t_admit"] - r["due"] for r in out["requests"]
                     if r["t_admit"] is not None and r["due"] > 0.75 * w]
        early_wait = [r["t_admit"] - r["due"] for r in out["requests"]
                      if r["t_admit"] is not None and r["due"] < 0.25 * w]
        ctx = {"kind": "serve", "window_s": w, "requests": out["requests"],
               "cap_s": w + 60}
        print(json.dumps({
            "rate": rate, "requests": len(reqs),
            "output_tok_s": sum(1 for r in out["requests"]
                                for t in r["times"] if t <= w) / w,
            "ttft_p50_ms": 1000 * ttft[len(ttft) // 2],
            "ttft_p95_ms": registry.metric_reader("ttft_p95_ms").read(ctx),
            "itl_p95_ms": registry.metric_reader("itl_p95_ms").read(ctx),
            "not_admitted_at_close": backlog,
            "wait_first_quarter_ms": 1000 * max(early_wait or [0]),
            "wait_last_quarter_ms": 1000 * max(late_wait or [0]),
            "stopped_s": out["stopped_s"]}), flush=True)
        for _ in range(100000):          # drain before the next rate
            if srv.engine.idle():
                break
            srv.engine.step(1e9)
    return 0


if __name__ == "__main__":
    sys.exit(main())
