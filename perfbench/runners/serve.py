"""Serving cells: open-loop traffic through the program's
``ContinuousBatcher`` with its block-sparse ``SparseLogitHead``.

Set-up makes the weights and the head on the device in one jitted call
from the seed (the head's block pattern from the configuration's
``pattern_seed``), builds the batcher at the configuration's slot and
pool sizes, and warms every prompt length of the mix and the decode step
through the batcher itself.  The window then offers the mix's requests at
their due times (seconds after the window opens) and calls
``ContinuousBatcher.step`` until the window has closed and every request
due in it has its first token.  Each output token is timed at the end of
the round that produced it.

``correct`` compares what the window served with the plain float32
reference: for a sample of finished requests drawn from the seed (the
longest among them), the widest gap by which a served token's reference
logit lies below the reference's best (greedy decoding, so a correct
program only departs from the best at near-ties).
"""

from __future__ import annotations

import gc
import time
from contextlib import nullcontext
from typing import Dict, List, Optional

import jax
import numpy as np

from harness import check, common, counters
from harness import traffic as gen
from harness.sparse_head import block_csr, head_blocks, pattern_digest
from repro.configs.base import ModelConfig
from repro.models import lm
from repro.serve import (BatcherConfig, ContinuousBatcher, Request,
                         RequestQueue, SamplingConfig, SparseLogitHead)

DRAIN_S = 60.0          # wait past the close for first tokens


def _spanned(fn, name):
    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


class Serve:
    """One served model: weights, head, batcher, warmed."""

    def __init__(self, cell, seed: int, *, head_fn=None):
        spec, ref = cell.config, cell.reference
        self.cell, self.spec, self.ref = cell, spec, ref
        self.cfg = ModelConfig(**ref.model_config_kwargs(spec))
        rows, cols = self.cfg.vocab_padded, self.cfg.d_model
        block = spec["head"]["block"]
        k_w, k_h = jax.random.split(common.seed_key(seed))
        mask = counters.head_mask(spec["head"]["pattern_seed"], rows, cols,
                                  block, spec["head"]["density"])
        make = jax.jit(lambda kw, kh: (ref.init_weights(spec, kw),
                                       head_blocks(spec, mask, kh)))
        self.weights, blocks = jax.block_until_ready(make(k_w, k_h))
        common.mark("weights")
        self.mask, self.block = mask, block
        self.head_w = block_csr(mask, blocks, block)
        self.head = SparseLogitHead.build(self.head_w)
        common.mark("head plan")
        sv = spec["serving"]
        longest = gen.longest_request(cell.traffic)
        if longest > sv["max_seq"]:
            raise ValueError(f"the mix's longest request ({longest}) is "
                             f"over serving.max_seq ({sv['max_seq']})")
        page, slots = sv["page_size"], sv["max_slots"]
        n_pages = (slots * -(-sv["max_seq"] // page) + 1
                   if lm.needs_kv_pages(self.cfg) else 2)
        self.bcfg = BatcherConfig(max_slots=slots, page_size=page,
                                  max_seq=sv["max_seq"], n_pages=n_pages)
        self.queue = RequestQueue(max_depth=1 << 20, max_seq=sv["max_seq"])
        self.engine = ContinuousBatcher(
            self.weights, self.cfg, self.queue, self.bcfg,
            SamplingConfig(temperature=0.0), head=self.head,
            key=k_h)
        jax.block_until_ready(self.engine.state)
        common.mark("batcher")

    # ------------------------------------------------------------------
    def warm(self, seed: int) -> None:
        """Every prompt length of the mix, its prefill and first sample,
        and the fused decode step with the head: through the batcher."""
        rng = np.random.default_rng(int(seed) + 1)
        lengths = self.cell.traffic["prompt"]["lengths"]
        reqs = [Request(tokens=rng.integers(0, self.cfg.vocab_size, n),
                        max_new_tokens=3, arrival=0.0) for n in lengths]
        if self.queue.submit_all(reqs) != len(reqs):
            raise RuntimeError("the queue refused a warm-up request")
        for _ in range(1000):
            if self.engine.idle():
                break
            self.engine.step(0.0)
        jax.block_until_ready(self.engine.state)
        common.mark("warm-up")

    def instrument(self) -> None:
        """Host spans around the calls into each layer (traced runs)."""
        e = self.engine
        e._step_fn = _spanned(e._step_fn, "bench.decode")
        e._head_fn = _spanned(e._head_fn, "bench.head")
        e._sample = _spanned(e._sample, "bench.sample")
        e._admit = _spanned(e._admit, "bench.admit")

    # ------------------------------------------------------------------
    def window(self, reqs: List[gen.Req], seconds: float,
               counter: Optional[common.CompileCounter] = None,
               traced: bool = False) -> Dict:
        eng, q = self.engine, self.queue
        sent = [Request(tokens=r.tokens, max_new_tokens=r.max_new,
                        arrival=r.due) for r in reqs]
        if q.submit_all(sent) != len(sent):
            raise RuntimeError("the queue refused a request of the mix")
        recs = {s.rid: {"rid": s.rid, "due": r.due, "prompt": r.tokens,
                        "max_new":
                        r.max_new, "t_admit": None, "times": [], "slot": None,
                        "status": None, "tokens": None}
                for s, r in zip(sent, reqs)}
        waiting = set(recs)
        rounds = []
        c0 = counter.total() if counter else 0
        cap = seconds + DRAIN_S

        def note(rid, n, t, t_admit, slot=None):
            rec = recs.get(rid)
            if rec is None:
                return
            if rec["t_admit"] is None:
                rec["t_admit"] = t_admit
            if slot is not None:
                rec["slot"] = slot
            rec["times"] += [t] * (n - len(rec["times"]))
            if rec["times"]:
                waiting.discard(rid)

        span = (jax.profiler.TraceAnnotation("bench.window") if traced
                else None)
        if span:
            span.__enter__()
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if (now >= seconds and not waiting) or now >= cap:
                break
            if eng.live() == 0 and q.peek_ready(now) is None:
                nxt = q.next_arrival()
                until = seconds if nxt is None else nxt
                if nxt is None and now >= seconds:
                    break
                with (jax.profiler.TraceAnnotation("bench.wait") if traced
                      else nullcontext()):
                    time.sleep(max(0.0, min(until - now, 0.05)))
                continue
            steps0 = eng.steps
            with (jax.profiler.TraceAnnotation("bench.step") if traced
                  else nullcontext()):
                done = eng.step(now)
            t_end = time.perf_counter() - t0
            rounds.append((now, t_end, eng.steps > steps0))
            for i, s in enumerate(eng.slots):
                if s is not None:
                    note(s.req.rid, len(s.out), t_end, s.t_admit, i)
            for c in done:
                note(c.rid, len(c.tokens), t_end, c.t_admit)
                if c.rid in recs:
                    recs[c.rid]["status"] = c.status
                    recs[c.rid]["tokens"] = list(c.tokens)
        if span:
            span.__exit__(None, None, None)
        jax.block_until_ready(eng.state)
        return {"requests": list(recs.values()), "rounds": rounds,
                "window_s": float(seconds),
                "compiles": (counter.total() - c0) if counter else 0,
                "stopped_s": time.perf_counter() - t0}

    def free_program_state(self) -> None:
        self.engine = None
        self.queue = None
        self.head = None
        gc.collect()

    def compare(self, out: Dict, seed: int, n_check: int,
                control: bool = False) -> Dict[str, List[float]]:
        """Widest served-token gap under the reference, per sampled
        finished request: the longest, then one from each batch slot in
        an order drawn from the seed, then any, up to ``n_check``."""
        done = [r for r in out["requests"]
                if r["tokens"] is not None and r["status"] == "length"]
        if not done:
            return {"program": [], "control": [], "n": 0}
        longest = max(range(len(done)), key=lambda i: (
            len(done[i]["prompt"]) + len(done[i]["tokens"])))
        rng = np.random.default_rng(int(seed) + 2)
        chosen, slots = [longest], {done[longest]["slot"]}
        rest = [int(i) for i in rng.permutation(len(done)) if i != longest]
        for i in rest:
            if done[i]["slot"] not in slots:
                chosen.append(i)
                slots.add(done[i]["slot"])
        chosen += [i for i in rest if i not in chosen]
        pick = [done[i] for i in chosen[:n_check]]
        seqs = [{"prompt": r["prompt"],
                 "served": np.asarray(r["tokens"], np.int32)} for r in pick]
        head = check.dense_from_blocks(self.head_w.blocks, self.mask,
                                       self.block)
        spec, ref = self.spec, self.ref
        res = check.served_gaps(
            lambda wt, t, cast: ref.hidden_states(wt, spec, t, cast),
            self.weights,
            head, seqs, vocab=self.cfg.vocab_size,
            max_len=self.bcfg.max_seq,
            max_out=int(self.cell.traffic["output"]["max"]),
            batch=int(self.cell.traffic["check"]["batch"]), control=control)
        res["n"] = len(pick)
        res["tokens"] = int(sum(len(s["served"]) for s in seqs))
        return res


def look(out: Dict, slots: int, max_seq: int) -> str:
    """What lies under the tails, for the run's record: the gaps between
    tokens split by whether their round also prefilled a request (a round
    that hands a request its first token ran its prefill), the time to
    first token's mean and median, and the share of the slots' tokens
    (``slots x max_seq``) that live requests held at each round's end."""
    w, reqs = out["window_s"], out["requests"]
    prefill_ends = {r["times"][0] for r in reqs if r["times"]}
    plain, stalled = [], []
    for r in reqs:
        for a, b in zip(r["times"], r["times"][1:]):
            if b <= w:
                (stalled if b in prefill_ends else plain).append(b - a)
    every = plain + stalled
    ttft = sorted((r["times"][0] if r["times"] else w + DRAIN_S)
                  - r["due"] for r in reqs)
    fill = []
    for _, t_end, _ in out["rounds"]:
        if t_end <= w:
            fill.append(sum(len(r["prompt"]) + sum(1 for t in r["times"]
                                                   if t <= t_end)
                            for r in reqs if r["t_admit"] is not None
                            and r["t_admit"] <= t_end
                            and not (r["times"] and r["times"][-1] < t_end
                                     and r["status"] is not None)))
    cap = float(slots * max_seq)

    def ms(v):
        return "none" if v is None else f"{1000.0 * v:.3f}"
    return (f"look: gaps {len(every)}, {len(stalled)} in rounds that "
            f"prefilled ({100.0 * len(stalled) / max(len(every), 1):.2f}%); "
            f"p95 ms all {ms(common.nearest_rank(every, 95))} "
            f"decode-only {ms(common.nearest_rank(plain, 95))} "
            f"prefill rounds {ms(common.nearest_rank(stalled, 95))}; "
            f"ttft ms mean {ms(sum(ttft) / len(ttft) if ttft else None)} "
            f"median {ms(common.nearest_rank(ttft, 50))}; live tokens of "
            f"slots x max_seq: max "
            f"{100.0 * max(fill, default=0) / cap:.2f}% mean "
            f"{100.0 * sum(fill) / max(len(fill), 1) / cap:.2f}%")


def run(cell, args, devices, counter) -> Dict:
    """One run of a serving cell; returns the context the metric readers
    and the result line take."""
    from harness import trace as tr
    traced = bool(args.trace)
    srv = Serve(cell, args.seed)
    srv.warm(args.seed)
    reqs = gen.serve_requests(cell.traffic, args.seed, args.seconds,
                              srv.cfg.vocab_size)
    common.mark("schedule")
    held_warm = common.bytes_in_use(devices)
    if traced:
        srv.instrument()
        tr.start(args.trace_dir)
    setup_s = common.elapsed_since_start()
    out = srv.window(reqs, args.seconds, counter, traced=traced)
    reduction = None
    if traced:
        reduction = tr.reduce(tr.load(tr.stop_and_find(args.trace_dir)))
    device = common.device_info(devices)
    held_after = common.bytes_in_use(devices)
    tokens_in = sum(1 for r in out["requests"] for t in r["times"]
                    if t <= out["window_s"])
    common.log(f"run: cell {cell.name} seed {args.seed} pattern "
               f"{pattern_digest(srv.mask)} live blocks "
               f"{int(srv.mask.sum())} requests due {len(reqs)} tokens in "
               f"window {tokens_in} rounds {len(out['rounds'])} compiles "
               f"in window {out['compiles']} stopped at "
               f"{out['stopped_s']:.3f} s")
    common.log(f"setup: {common.setup_stages()}; bytes in use after "
               f"warm-up {held_warm}, after the window {held_after}, peak "
               f"{device['memory_peak_bytes']}")
    common.log(look(out, srv.bcfg.max_slots, srv.bcfg.max_seq))
    srv.free_program_state()
    res = srv.compare(out, args.seed, int(cell.traffic["check"]["requests"]))
    gap = max(res["program"]) if res["program"] else float("inf")
    common.log(f"compare: {res['n']} requests, {res.get('tokens', 0)} "
               f"served tokens, widest gap per request "
               f"{common.fmt_list(res['program'])}")
    limit = float(cell.config["limits"]["logit_gap"])
    checks = {"logit_gap": {"value": gap, "limit": limit}}
    failed = sum(1 for r in out["requests"] if not r["times"]
                 or (r["status"] is not None and r["status"] != "length"))
    return {"kind": "serve", "cell": cell.name, "setup_s": setup_s,
            "window_s": out["window_s"], "requests": out["requests"],
            "rounds": out["rounds"],
            "cap_s": out["window_s"] + DRAIN_S,
            "peak": None, "trace": reduction, "device": device,
            "checks": checks, "attempted": len(reqs), "failed": failed}
