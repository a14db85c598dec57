"""Head-distillation cells: the forward and VJP of the trainable
block-sparse head (``SparseLogitHead.build(trainable=True)``), as
logit-distillation fine-tuning against the serving head runs it.

Set-up makes the head (pattern from the configuration's ``pattern_seed``,
payload from the seed), the hidden tokens and a distillation-loss
cotangent ``softmax(z_student) - softmax(z_teacher)`` over the real
vocabulary, all on the device in one jitted call, builds the train plan
once and compiles the call.  The window issues calls back to back (at
most ``in_flight`` outstanding) and ends at ``block_until_ready`` of the
last one.

``correct`` compares the last call's logits, ``dA`` and ``dh`` with a
plain float32 dense product under HIGHEST precision, block-row panel by
panel: max |out - ref| / max |ref| for each.
"""

from __future__ import annotations

import collections
import gc
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness import common, counters
from harness.check import F32, HIGHEST, fp8_round
from harness.sparse_head import block_csr, head_blocks, pattern_digest
from repro.configs.base import ModelConfig
from repro.core.csr import BlockCSR
from repro.serve import SparseLogitHead

OUTPUTS = ("logits", "dA", "dh")


class HeadVJP:
    def __init__(self, cell, seed: int):
        spec, tr = cell.config, cell.traffic
        self.cell, self.spec = cell, spec
        cfg = ModelConfig(**cell.reference.model_config_kwargs(spec))
        self.vocab, self.rows, self.cols = (cfg.vocab_size, cfg.vocab_padded,
                                            cfg.d_model)
        self.block = spec["head"]["block"]
        self.mask = counters.head_mask(spec["head"]["pattern_seed"],
                                       self.rows, self.cols, self.block,
                                       spec["head"]["density"])
        b, s = tr["sequences"], tr["tokens_per_sequence"]
        self.tokens = b * s
        vocab, rows, cols, mask = self.vocab, self.rows, self.cols, self.mask

        def make(key):
            k_a, k_h, k_s, k_t = jax.random.split(key, 4)
            blocks = head_blocks(spec, mask, k_a)
            h = jax.random.normal(k_h, (b, s, cols), F32).astype(jnp.bfloat16)
            live = jnp.arange(rows) < vocab

            def probs(k):
                z = jax.random.normal(k, (b, s, rows), F32)
                return jax.nn.softmax(jnp.where(live, z, -jnp.inf), -1)
            dc = (probs(k_s) - probs(k_t)).astype(jnp.bfloat16)
            return blocks, h, dc

        self.blocks, self.h, self.dc = jax.block_until_ready(
            jax.jit(make)(common.seed_key(seed)))
        common.mark("operands")
        w = block_csr(mask, self.blocks, self.block)
        self.plan = SparseLogitHead.build(w, trainable=True).plan
        common.mark("plan")
        meta = (w.block_col, w.block_row, w.row_ptr, w.shape, w.block_shape)
        plan = self.plan

        def fwd_vjp(blocks, h, dc):
            def f(bl, hh):
                return SparseLogitHead(BlockCSR(bl, *meta), plan)(hh)
            out, vjp = jax.vjp(f, blocks, h)
            d_blocks, dh = vjp(dc)
            return out, d_blocks, dh

        self.call = jax.jit(fwd_vjp)
        self.in_flight = int(tr["in_flight"])

    def warm(self) -> None:
        jax.block_until_ready(self.call(self.blocks, self.h, self.dc))
        common.mark("warm-up")

    def window(self, seconds: float, counter=None, traced: bool = False
               ) -> Dict:
        c0 = counter.total() if counter else 0
        q = collections.deque()
        n = 0
        span = (jax.profiler.TraceAnnotation("bench.window") if traced
                else None)
        if span:
            span.__enter__()
        t0 = time.perf_counter()
        while True:
            q.append(self.call(self.blocks, self.h, self.dc))
            n += 1
            if len(q) > self.in_flight:
                if traced:
                    with jax.profiler.TraceAnnotation("bench.block"):
                        jax.block_until_ready(q.popleft())
                else:
                    jax.block_until_ready(q.popleft())
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(q[-1])
        t1 = time.perf_counter()
        if span:
            span.__exit__(None, None, None)
        last = q[-1]
        q.clear()
        return {"calls": n, "window_s": t1 - t0, "last": last,
                "compiles": (counter.total() - c0) if counter else 0}

    def compare(self, outputs, control: bool = False) -> Dict[str, float]:
        """Relative error of each output against the float32 reference;
        with ``control`` the reference itself, its operands rounded
        through fp8, stands in for the program's outputs."""
        if outputs is None:          # the control stands in for them
            outputs = (self.dc, self.blocks, self.h)
        logits, d_blocks, dh = outputs
        mask, blk = self.mask, self.block
        gm, gk = mask.shape
        rows_l, cols_l = np.nonzero(mask)
        panel = max(d for d in range(1, min(gm, 100) + 1) if gm % d == 0)
        d_model = self.cols
        h = self.h.reshape(-1, d_model).astype(F32)
        dc = self.dc.reshape(self.tokens, -1)
        logits = logits.reshape(self.tokens, -1)
        dh = dh.reshape(-1, d_model)

        @jax.jit
        def one_panel(w_p, da_p, h, dc_p, out_p, mask_p):
            def dense(t):
                return t.astype(F32).transpose(0, 2, 1, 3).reshape(
                    panel * blk, gk * blk)
            m = jnp.repeat(jnp.repeat(mask_p, blk, 0), blk, 1)
            w, dcf = dense(w_p), dc_p.astype(F32)
            ex_l = jnp.dot(h, w.T, precision=HIGHEST)
            ex_dh = jnp.dot(dcf, w, precision=HIGHEST)
            ex_da = jnp.dot(dcf.T, h, precision=HIGHEST) * m
            if control:
                wq, hq, dq = fp8_round(w), fp8_round(h), fp8_round(dcf)
                got_l = jnp.dot(hq, wq.T, precision=HIGHEST)
                got_dh = jnp.dot(dq, wq, precision=HIGHEST)
                got_da = jnp.dot(dq.T, hq, precision=HIGHEST) * m
            else:
                got_l, got_da, got_dh = out_p.astype(F32), dense(da_p), ex_dh
            return (jnp.max(jnp.abs(got_l - ex_l)), jnp.max(jnp.abs(ex_l)),
                    jnp.max(jnp.abs(got_da - ex_da)), jnp.max(jnp.abs(ex_da)),
                    got_dh, ex_dh)

        tiles = jnp.zeros((gm, gk, blk, blk), jnp.bfloat16)
        w_tiles = tiles.at[rows_l, cols_l].set(self.blocks[:rows_l.size])
        da_tiles = tiles.at[rows_l, cols_l].set(
            d_blocks[:rows_l.size].astype(jnp.bfloat16))
        del tiles
        err = {k: 0.0 for k in OUTPUTS}
        mx = {k: 0.0 for k in OUTPUTS}
        dh_ref = jnp.zeros((self.tokens, d_model), F32)
        dh_got = jnp.zeros((self.tokens, d_model), F32)
        for i in range(0, gm, panel):
            r = slice(i * blk, (i + panel) * blk)
            e_l, m_l, e_a, m_a, dh_g, dh_r = one_panel(
                w_tiles[i:i + panel], da_tiles[i:i + panel], h, dc[:, r],
                logits[:, r], jnp.asarray(mask[i:i + panel]))
            err["logits"] = max(err["logits"], float(e_l))
            mx["logits"] = max(mx["logits"], float(m_l))
            err["dA"] = max(err["dA"], float(e_a))
            mx["dA"] = max(mx["dA"], float(m_a))
            dh_ref = dh_ref + dh_r
            dh_got = dh_got + dh_g
        if not control:
            dh_got = dh.astype(F32)
        err["dh"] = float(jnp.max(jnp.abs(dh_got - dh_ref)))
        mx["dh"] = float(jnp.max(jnp.abs(dh_ref)))
        return {k: err[k] / max(mx[k], 1e-30) for k in OUTPUTS}

    def free_program_state(self) -> None:
        self.call = None
        self.plan = None
        gc.collect()


def run(cell, args, devices, counter) -> Dict:
    from harness import trace as tr
    traced = bool(args.trace)
    hv = HeadVJP(cell, args.seed)
    hv.warm()
    held_warm = common.bytes_in_use(devices)
    if traced:
        tr.start(args.trace_dir)
    setup_s = common.elapsed_since_start()
    out = hv.window(args.seconds, counter, traced=traced)
    reduction = None
    if traced:
        reduction = tr.reduce(tr.load(tr.stop_and_find(args.trace_dir)))
    device = common.device_info(devices)
    held_after = common.bytes_in_use(devices)
    common.log(f"run: cell {cell.name} seed {args.seed} pattern "
               f"{pattern_digest(hv.mask)} live blocks {int(hv.mask.sum())} "
               f"calls in window {out['calls']} window "
               f"{out['window_s']:.6f} s compiles in window "
               f"{out['compiles']}")
    common.log(f"setup: {common.setup_stages()}; bytes in use after "
               f"warm-up {held_warm}, after the window {held_after}, peak "
               f"{device['memory_peak_bytes']}")
    work = counters.spmm_vjp_work(int(hv.mask.sum()), hv.block, hv.tokens,
                                  hv.rows, hv.cols)
    util = counters.plan_utilization(hv.plan)
    last = out.pop("last")
    hv.free_program_state()
    errs = hv.compare(last)
    del last
    limits = cell.config["limits"]
    checks = {f"head_{k}_rel_err": {"value": errs[k],
                                    "limit": float(limits[f"head_{k}_rel_err"])}
              for k in OUTPUTS}
    return {"kind": "head_vjp", "cell": cell.name, "setup_s": setup_s,
            "window_s": out["window_s"], "calls": out["calls"],
            "work": work, "plan_utilization": util, "trace": reduction,
            "device": device, "checks": checks, "attempted": out["calls"],
            "failed": 0}
