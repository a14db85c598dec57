"""Serving-engine benchmark: the continuous batcher under Poisson load.

Each scenario takes a smoke config from ``src/repro/configs`` (the
architecture matrix: dense global attention, local-window + RG-LRU,
pure SSM, MoE — and the block-sparse logit head riding the dense
config), submits a fixed-seed Poisson arrival process to the
:class:`~repro.serve.ContinuousBatcher`, and reports two kinds of
numbers:

* **wall-clock** — tokens/sec and p50/p99 request latency in ms
  (latency-in-steps × measured ms/step).  Interpret-mode CPU timing:
  correctness-grade, recorded in the json artifact, **never gated**.
* **deterministic** — pure scheduling arithmetic on the virtual step
  clock (arrivals are in *step* units, ``eos_id=-1`` so token counts
  are workload properties, not model properties): fused steps, tokens
  served, admissions, peak KV pages vs the static ``slots × max_pages``
  equivalent, mean slot occupancy, p50/p99 latency in steps.  These are
  bit-reproducible across machines and jax versions, so the ``--check``
  gate compares them **exactly** against the checked-in
  ``BENCH_serve.json`` baseline.

``--smoke`` runs the golden scenario subset for CI (identical workloads
to the baseline run — the gate only means something when the arrival
process matches bit-for-bit); the full run adds heavier, ungated load
scenarios.  Refresh the baseline with::

    PYTHONPATH=src python benchmarks/serve_bench.py --json BENCH_serve.json
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.launch import compile_cache
from repro.models import lm
from repro.models.layers import init_sparse_linear
from repro.serve import (BatcherConfig, ContinuousBatcher, FaultSchedule,
                         Request, RequestQueue, SparseLogitHead)
from repro.serve.faults import apply_malformed
from repro.serve.paged_cache import pages_for

RECORDS: list = []

# the scenario matrix every gated run (smoke included) must emit —
# coverage is checked both ways, so a scenario that stops running
# fails the gate instead of silently shrinking it
SMOKE_GOLDEN_NAMES = ("serve_qwen3-4b", "serve_recurrentgemma-9b",
                      "serve_mamba2-2.7b", "serve_qwen3-4b_sparse_head",
                      "serve_qwen3-4b_chaos")

# scheduling arithmetic only — bit-reproducible, gated by exact match.
# Wall-clock keys (tokens_per_sec, *_ms) are schema'd but never gated.
# The failure-semantics counters are deterministic too (faults are keyed
# on the virtual round clock), so the chaos scenario's preemptions /
# sheds / retries / quarantines gate exactly like the scheduling keys —
# and their forced zeros on the fault-free scenarios pin "no fault
# machinery engages on a healthy workload".
GOLDEN_KEYS = ("steps", "tokens", "admitted", "rejected", "peak_pages",
               "static_equiv_pages", "reclaimed", "occupancy",
               "p50_latency_steps", "p99_latency_steps",
               "preemptions", "sheds", "expired", "quarantined", "errors",
               "retries", "fallbacks")


def _poisson_workload(cfg, rng, *, n_req: int, rate: float,
                      prompt_hi: int = 16, new_hi: int = 16):
    """Fixed-seed Poisson arrival process in step-clock units."""
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_req))
    prompt_lens = rng.integers(4, prompt_hi + 1, n_req)
    max_news = rng.integers(4, new_hi + 1, n_req)
    reqs = []
    for i in range(n_req):
        toks = rng.integers(0, cfg.vocab_size, int(prompt_lens[i]))
        reqs.append(Request(tokens=toks.astype(np.int32),
                            max_new_tokens=int(max_news[i]),
                            arrival=float(arrivals[i])))
    return reqs


def _pool_for(cfg, reqs, *, max_slots: int, page_size: int):
    """Pool size covering the workload's worst concurrent pinning: the
    ``max_slots`` largest per-request footprints (window-bounded for
    local/recurrent configs), so decode-page growth can never exhaust
    the pool mid-flight.  Stays well under the static per-slot
    equivalent whenever requests are shorter than ``max_seq``."""
    horizon = lm.history_horizon(cfg)
    if not lm.needs_kv_pages(cfg):
        return 2                       # dead page + one (never touched)
    foots = []
    for r in reqs:
        f = pages_for(r.prompt_len + r.max_new_tokens, page_size)
        if horizon is not None:
            f = min(f, pages_for(max(horizon, 1), page_size) + 2)
        foots.append(f)
    worst = sum(sorted(foots)[-max_slots:])
    return worst + 2                   # dead page + one page of slack


def run_scenario(name: str, arch: str, *, seed: int, n_req: int,
                 rate: float, max_slots: int = 4, page_size: int = 4,
                 sparse_head: bool = False, chaos: bool = False):
    cfg = get_smoke_config(arch)
    rng = np.random.default_rng(seed)
    reqs = _poisson_workload(cfg, rng, n_req=n_req, rate=rate)
    max_seq = max(r.prompt_len + r.max_new_tokens for r in reqs)
    max_seq = pages_for(max_seq, page_size) * page_size
    n_pages = _pool_for(cfg, reqs, max_slots=max_slots,
                        page_size=page_size)

    faults = None
    if chaos:
        # seeded chaos: transient step bursts (some past the retry
        # budget), NaN poisoning, allocator denial, malformed prompts —
        # all keyed on the round clock, so the counters gate exactly
        faults = FaultSchedule.sample(
            seed, 64, p_transient=0.1, max_burst=3, p_poison=0.08,
            max_slot=max_slots, p_deny=0.08, n_requests=n_req,
            p_malformed=0.15)
        apply_malformed(reqs, faults, cfg.vocab_size, seed=seed)
        # deadlines on a deterministic third of the workload: tight
        # enough that backpressure (denial rounds, fallback drains)
        # sheds some of them
        for i, r in enumerate(reqs):
            if i % 3 == 1:
                r.deadline = r.arrival + 12.0
        # shrink the pool below the worst case to force preemption, but
        # never below what the largest single request needs to finish
        # alone (anything less is a capacity bug, not a schedulable load)
        biggest = max(pages_for(r.prompt_len + r.max_new_tokens,
                                page_size) for r in reqs)
        n_pages = max(biggest + 3, int(0.6 * n_pages))

    head = None
    if sparse_head:
        head = SparseLogitHead.build(init_sparse_linear(
            jax.random.PRNGKey(7), cfg.d_model, cfg.vocab_padded,
            block_shape=(64, 64), block_density=0.5))

    queue = RequestQueue()
    assert queue.submit_all(reqs) == len(reqs)
    eng = ContinuousBatcher(
        params=lm.init_params(cfg, jax.random.PRNGKey(0)), cfg=cfg,
        queue=queue,
        bcfg=BatcherConfig(max_slots=max_slots, page_size=page_size,
                           n_pages=n_pages, max_seq=max_seq),
        head=head, faults=faults)

    # drive on the virtual step clock, timing each fused step.  The
    # first steps carry compilation; ms/step uses the post-warmup tail.
    step_walls = []
    t = 0
    t0 = time.perf_counter()
    while not eng.idle():
        s = time.perf_counter()
        eng.step(float(t))
        step_walls.append(time.perf_counter() - s)
        t += 1
        if t > 100_000:
            raise RuntimeError(f"{name}: engine did not drain")
    wall = time.perf_counter() - t0
    comps = eng.completions
    assert len(comps) == n_req, (len(comps), n_req)

    tokens = sum(len(c.tokens) for c in comps)
    lat_steps = np.asarray([c.latency for c in comps])
    warm = step_walls[len(step_walls) // 2:]        # skip compile ramp
    ms_step = 1e3 * float(np.median(warm)) if warm else 0.0
    stats = eng.memory_stats()

    rec = {
        "name": name,
        # ---- wall clock (reported, never gated) ----
        "tokens_per_sec": round(tokens / wall, 1),
        "ms_per_step": round(ms_step, 2),
        "p50_latency_ms": round(float(np.percentile(lat_steps, 50))
                                * ms_step, 1),
        "p99_latency_ms": round(float(np.percentile(lat_steps, 99))
                                * ms_step, 1),
        # ---- deterministic scheduling metrics (gated exactly) ----
        "steps": eng.steps,
        "tokens": tokens,
        "admitted": eng.admitted,
        "rejected": queue.rejected_depth + queue.rejected_shape,
        "peak_pages": stats["peak_pages"],
        "pool_pages": stats["pool_pages"],
        "static_equiv_pages": stats["static_equiv_pages"],
        "reclaimed": stats["reclaimed"],
        "occupancy": round(eng.occupancy_sum / max(eng.steps, 1), 4),
        "p50_latency_steps": round(float(np.percentile(lat_steps, 50)), 3),
        "p99_latency_steps": round(float(np.percentile(lat_steps, 99)), 3),
        "sparse_head": bool(sparse_head),
        "chaos": bool(chaos),
    }
    rec.update(eng.fault_stats())      # deterministic, gated on EVERY
    #                                    scenario (zeros pin the healthy
    #                                    path; non-zeros pin the chaos)
    RECORDS.append(rec)
    print(f"{name},{rec['tokens_per_sec']},steps={rec['steps']}"
          f"/tok={tokens}/peak_pg={rec['peak_pages']}"
          f"of{rec['static_equiv_pages']}"
          f"/occ={rec['occupancy']:.2f}"
          f"/p99={rec['p99_latency_steps']:.0f}st"
          + (f"/pre={rec['preemptions']}/shed={rec['sheds']}"
             f"/quar={rec['quarantined']}/retry={rec['retries']}"
             f"/fb={rec['fallbacks']}" if chaos else ""))
    # the paged-memory claim, asserted on every scenario that has a KV
    # at all: peak allocation under the static per-slot equivalent
    if lm.needs_kv_pages(eng.cfg):
        assert 0 < rec["peak_pages"] < rec["static_equiv_pages"], rec
    if chaos:
        # the chaos must actually bite, or the scenario gates nothing
        assert (rec["quarantined"] + rec["retries"] + rec["preemptions"]
                + rec["sheds"] + rec["errors"]) > 0, rec
    assert eng.allocator.in_use == 0


def run(smoke: bool = False):
    print("name,tokens_per_sec,derived")
    # golden scenarios: IDENTICAL parameters in smoke and full runs, so
    # the exact-match gate compares like with like
    run_scenario("serve_qwen3-4b", "qwen3-4b", seed=0, n_req=10,
                 rate=0.3)
    run_scenario("serve_recurrentgemma-9b", "recurrentgemma-9b", seed=1,
                 n_req=10, rate=0.3)
    run_scenario("serve_mamba2-2.7b", "mamba2-2.7b", seed=2, n_req=10,
                 rate=0.3)
    run_scenario("serve_qwen3-4b_sparse_head", "qwen3-4b", seed=3,
                 n_req=10, rate=0.3, sparse_head=True)
    run_scenario("serve_qwen3-4b_chaos", "qwen3-4b", seed=7, n_req=12,
                 rate=0.5, chaos=True)
    if smoke:
        return
    # heavier load points (reported in the json, not golden-gated):
    # saturation (arrivals faster than slots drain) and a wide-slot run
    run_scenario("serve_qwen3-4b_saturated", "qwen3-4b", seed=4,
                 n_req=24, rate=1.5)
    run_scenario("serve_qwen3-4b_slots8", "qwen3-4b", seed=5, n_req=24,
                 rate=0.6, max_slots=8)
    run_scenario("serve_granite-moe-3b-a800m", "granite-moe-3b-a800m",
                 seed=6, n_req=10, rate=0.3)


def check_against(baseline_path: str) -> int:
    """Exact-match gate over the deterministic scheduling metrics.

    The metrics are pure arithmetic on a fixed-seed arrival process —
    any drift is a scheduler/allocator behavior change, so the gate is
    equality, not a tolerance band.  Coverage runs both ways: every
    golden scenario this run produced must exist in the baseline, and
    every ``SMOKE_GOLDEN_NAMES`` entry must appear in this run.  Wall
    clock is never gated.  Refresh with:
    ``PYTHONPATH=src python benchmarks/serve_bench.py --json
    BENCH_serve.json``.
    """
    with open(baseline_path) as f:
        baseline = {r["name"]: r for r in json.load(f)["records"]}
    failures = []
    checked = 0
    produced = {r["name"] for r in RECORDS}
    for name in SMOKE_GOLDEN_NAMES:
        if name not in produced:
            failures.append(f"{name}: expected golden scenario was not "
                            f"run — matrix shrank?")
    for rec in RECORDS:
        base = baseline.get(rec["name"])
        if base is None:
            failures.append(f"{rec['name']}: scenario missing from "
                            f"baseline — refresh {baseline_path}")
            continue
        for key in GOLDEN_KEYS:
            if key not in base:
                failures.append(f"{rec['name']}.{key}: missing from "
                                f"baseline — refresh {baseline_path}")
                continue
            checked += 1
            if rec[key] != base[key]:
                failures.append(
                    f"{rec['name']}.{key}: {rec[key]} != baseline "
                    f"{base[key]} (scheduling drift — refresh "
                    f"{baseline_path} if intended)")
    print(f"# check: {checked} deterministic serve metrics vs "
          f"{baseline_path}", file=sys.stderr)
    if failures:
        for msg in failures:
            print(f"# REGRESSION {msg}", file=sys.stderr)
        return 1
    if checked == 0:
        print("# REGRESSION check matched no scenarios (baseline "
              "stale?)", file=sys.stderr)
        return 1
    return 0


def _git_rev() -> str:
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10, check=True).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", metavar="PATH",
                    help="write machine-readable records to PATH")
    ap.add_argument("--smoke", action="store_true",
                    help="golden scenario subset (CI)")
    ap.add_argument("--check", metavar="BASELINE",
                    help="fail when deterministic scheduling metrics "
                         "drift from BASELINE json")
    args = ap.parse_args(argv)
    compile_cache.enable()

    run(smoke=args.smoke)

    if args.json:
        payload = {"schema": 1, "smoke": bool(args.smoke),
                   "backend": jax.default_backend(),
                   "git_rev": _git_rev(), "records": RECORDS}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        print(f"# wrote {len(RECORDS)} records to {args.json}"
              f" (rev {payload['git_rev']})", file=sys.stderr)
    if args.check:
        return check_against(args.check)
    return 0


if __name__ == "__main__":
    sys.exit(main())
