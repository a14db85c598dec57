"""Bring-up proof on a TPU: the Maple kernels and qwen3-4b serving, compiled
for and run on the chip, each checked against a reference computed on the
same chip.

    python chip_smoke.py            # one chip: SpMM fwd+grad, SpGEMM, serving
    python chip_smoke.py --chips 4  # four chips: the partitioned SpMM only

One process holds the chip for its whole life and starts no children.  It
exits non-zero, without a result line, when JAX finds no TPU or when any
phase misses its reference; there is no CPU fallback.  Each phase prints one
line; the last line of stdout is one JSON object naming the device as JAX
reports it.  Sizes are fixed and every input is drawn from ``--seed``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.csr import CSR, BlockCSR  # noqa: E402
from repro.distributed.sharding import partition_mesh  # noqa: E402
from repro.kernels import (maple_spgemm, maple_spmm, plan_spgemm,  # noqa: E402
                           plan_spmm_vjp)
from repro.launch import compile_cache  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve import (BatcherConfig, ContinuousBatcher,  # noqa: E402
                         Request, RequestQueue, SamplingConfig,
                         SparseLogitHead, generate, jitted_decode_step)

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32

# SpMM: a power-law 8192 x 8192 BlockCSR of MXU-sized blocks, N = 512
SPMM_GRID, SPMM_BLOCK, SPMM_N = 64, 128, 512
# SpGEMM: C = A·A on a power-law CSR; rows capped so ELL panels stay small
SPGEMM_N, SPGEMM_ROW_CAP = 4096, 64
# serving: qwen3-4b at published widths, 8 greedy requests
SERVE_ARCH, PROMPT_LENS, NEW_TOKENS, SLOTS = "qwen3-4b", (128, 256), 32, 8
HEAD_BLOCK, HEAD_DENSITY = 128, 0.5
# outputs are bf16 (8 mantissa bits); f32 paths are held ~100x tighter
BF16_TOL, F32_TOL = 1e-2, 1e-4


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def _rel_err(x, ref) -> float:
    """max |x - ref| / max |ref|, computed on the device."""
    ref = jnp.asarray(ref, F32)
    num = jnp.max(jnp.abs(jnp.asarray(x, F32) - ref))
    return float(num / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))


def _compile_with_kernel(fn, *args):
    """AOT-compile ``fn`` for the chip and assert a Mosaic kernel is in
    the program — proof the Pallas call lowered instead of interpreting."""
    compiled = jax.jit(fn).lower(*args).compile()
    _check("tpu_custom_call" in compiled.as_text(),
           f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in the "
           f"compiled HLO — the kernel did not lower for the chip")
    return compiled


def power_law_lengths(rng, n: int, cap: int, alpha: float) -> np.ndarray:
    """Row lengths ~ Zipf(alpha), each in [1, cap]."""
    return np.minimum(rng.zipf(alpha, n), cap)


def block_csr(mask: np.ndarray, block: int, blocks: jax.Array) -> BlockCSR:
    """BlockCSR over a boolean block mask, payload drawn on the device."""
    gm, gk = mask.shape
    rows, cols = np.nonzero(mask)
    row_ptr = np.zeros(gm + 1, np.int32)
    np.cumsum(np.bincount(rows, minlength=gm), out=row_ptr[1:])
    return BlockCSR(blocks=blocks, block_col=jnp.asarray(cols, jnp.int32),
                    block_row=jnp.asarray(rows, jnp.int32),
                    row_ptr=jnp.asarray(row_ptr),
                    shape=(gm * block, gk * block),
                    block_shape=(block, block))


def spmm_operands(seed: int):
    """Power-law BlockCSR A (bf16), dense B and output cotangent dC."""
    rng = np.random.default_rng(seed)
    g, bs = SPMM_GRID, SPMM_BLOCK
    mask = np.zeros((g, g), bool)
    for i, ln in enumerate(power_law_lengths(rng, g, g, 1.6)):
        mask[i, rng.choice(g, ln, replace=False)] = True
    k_a, k_b, k_dc = jax.random.split(jax.random.PRNGKey(seed), 3)
    nnzb = int(mask.sum())
    a = block_csr(mask, bs, jax.random.normal(k_a, (nnzb, bs, bs),
                                              jnp.bfloat16))
    b = jax.random.normal(k_b, (g * bs, SPMM_N), jnp.bfloat16)
    dc = jax.random.normal(k_dc, (g * bs, SPMM_N), jnp.bfloat16)
    return a, b, dc


def spmm_fwd_vjp(plan):
    """Jittable (A, B, dC) -> (C, dA.blocks, dB) through maple_spmm's
    custom VJP on a prebuilt train plan, compiled (never interpreted)."""
    def fwd_vjp(a, b, dc):
        def f(blocks, bb):
            aa = BlockCSR(blocks, a.block_col, a.block_row, a.row_ptr,
                          a.shape, a.block_shape)
            return maple_spmm(aa, bb, plan=plan, interpret=False)
        out, vjp = jax.vjp(f, a.blocks, b)
        da, db = vjp(dc)
        return out, da, db
    return fwd_vjp


@jax.jit
def _spmm_reference(a, b, dc):
    bm, bk = a.block_shape
    gm, gk = a.n_block_rows, a.n_block_cols
    dense = a.to_dense().astype(F32)
    out = jnp.dot(dense, b.astype(F32), precision=HIGHEST)
    db = jnp.dot(dense.T, dc.astype(F32), precision=HIGHEST)
    da = jnp.dot(dc.astype(F32), b.astype(F32).T, precision=HIGHEST)
    da = da.reshape(gm, bm, gk, bk).transpose(0, 2, 1, 3)
    return out, da[a.block_row, a.block_col], db


def phase_spmm(seed: int) -> None:
    a, b, dc = spmm_operands(seed)
    train = plan_spmm_vjp(a)
    _check(train.fwd.fused == "compact" and train.bwd.fused == "compact",
           "the default plan must take the compact layout")
    run = _compile_with_kernel(spmm_fwd_vjp(train), a, b, dc)
    out, da, db = run(a, b, dc)
    ref_out, ref_da, ref_db = _spmm_reference(a, b, dc)
    errs = [_rel_err(out, ref_out), _rel_err(da, ref_da),
            _rel_err(db, ref_db)]
    _check(all(e < BF16_TOL for e in errs),
           f"spmm fwd/dA/dB rel err {errs} >= {BF16_TOL}")
    m, k = a.shape
    print(f"spmm fwd+grad: {m}x{k} power-law BlockCSR, "
          f"{int(a.row_ptr[-1])} blocks of {SPMM_BLOCK}x{SPMM_BLOCK}, "
          f"N={SPMM_N}, bf16, compact plan x{train.fwd.n_lanes} lanes | "
          f"rel err vs HIGHEST dense: fwd {errs[0]:.2e} dA {errs[1]:.2e} "
          f"dB {errs[2]:.2e} (tol {BF16_TOL:g}) ok", flush=True)


def spgemm_operand(seed: int) -> CSR:
    rng = np.random.default_rng(seed + 1)
    n = SPGEMM_N
    lens = power_law_lengths(rng, n, SPGEMM_ROW_CAP, 1.8)
    row_ptr = np.zeros(n + 1, np.int32)
    np.cumsum(lens, out=row_ptr[1:])
    cols = np.concatenate([np.sort(rng.choice(n, ln, replace=False))
                           for ln in lens]).astype(np.int32)
    vals = rng.standard_normal(cols.size).astype(np.float32)
    return CSR(value=jnp.asarray(vals), col_id=jnp.asarray(cols),
               row_ptr=jnp.asarray(row_ptr), shape=(n, n))


def phase_spgemm(seed: int) -> None:
    a = spgemm_operand(seed)
    plan = plan_spgemm(a, a)

    def square(x):
        return maple_spgemm(x, x, plan=plan, interpret=False).to_dense()

    c = _compile_with_kernel(square, a)(a)
    dense = a.to_dense()
    ref = jax.jit(lambda d: jnp.dot(d, d, precision=HIGHEST))(dense)
    err = _rel_err(c, ref)
    _check(err < F32_TOL, f"spgemm rel err {err:.3e} >= {F32_TOL}")
    print(f"spgemm C=A*A: {SPGEMM_N}x{SPGEMM_N} power-law CSR, "
          f"nnz(A)={int(a.row_ptr[-1])}, nnz(C)={plan.nnz_c}, "
          f"f32, {plan.n_lanes} lanes | rel err vs HIGHEST dense "
          f"{err:.2e} (tol {F32_TOL:g}) ok", flush=True)


def sparse_head(cfg, key) -> SparseLogitHead:
    """(vocab_padded, d_model) block-sparse unembedding, every block-row
    kept, block density ~HEAD_DENSITY, bf16 payload drawn on the device."""
    gm, gk = cfg.vocab_padded // HEAD_BLOCK, cfg.d_model // HEAD_BLOCK
    k_mask, k_val = jax.random.split(key)
    mask = np.asarray(jax.random.uniform(k_mask, (gm, gk))) < HEAD_DENSITY
    mask[np.arange(gm), np.arange(gm) % gk] = True
    blocks = jax.random.normal(
        k_val, (int(mask.sum()), HEAD_BLOCK, HEAD_BLOCK), jnp.bfloat16)
    scale = (HEAD_DENSITY * cfg.d_model) ** -0.5    # unit-variance logits
    return SparseLogitHead.build(block_csr(mask, HEAD_BLOCK, blocks * scale))


def phase_serving(seed: int) -> None:
    compile_s = []

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    cfg = get_config(SERVE_ARCH)
    k_init, k_head = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.jit(lm.init_params, static_argnums=(0, 2))(
        cfg, k_init, jnp.bfloat16)
    head = sparse_head(cfg, k_head)

    rng = np.random.default_rng(seed + 2)
    lens = [PROMPT_LENS[i % len(PROMPT_LENS)] for i in range(SLOTS)]
    reqs = [Request(tokens=rng.integers(0, cfg.vocab_size, n),
                    max_new_tokens=NEW_TOKENS) for n in lens]
    max_seq, page = max(PROMPT_LENS) + NEW_TOKENS, 16
    bcfg = BatcherConfig(max_slots=SLOTS, page_size=page, max_seq=max_seq,
                         n_pages=SLOTS * -(-max_seq // page) + 1)
    queue = RequestQueue(max_seq=bcfg.max_seq)
    _check(queue.submit_all(reqs) == len(reqs), "queue refused a request")
    sampling = SamplingConfig(max_new_tokens=NEW_TOKENS)
    engine = ContinuousBatcher(params, cfg, queue, bcfg, sampling,
                               head=head)
    done = sorted(engine.run(), key=lambda c: c.rid)
    _check(len(done) == len(reqs), f"{len(done)} of {len(reqs)} completed")
    for c in done:
        toks = np.asarray(c.tokens)
        _check(c.status == "length" and toks.size == NEW_TOKENS,
               f"request {c.rid}: status {c.status!r}, {toks.size} tokens")
        _check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
               f"request {c.rid}: token outside [0, {cfg.vocab_size})")

    # the head on one decode batch, against a dense product on the chip
    step = jitted_decode_step(cfg, paged=True, return_hidden=True)
    last = jnp.asarray([[c.tokens[-1]] for c in done], jnp.int32)
    hidden, _ = step(params, state=engine.state, tokens=last)
    logits = jax.jit(lambda w, h: SparseLogitHead(w, head.plan)(h))(
        head.weight, hidden)
    w_dense = head.weight.to_dense()
    ref = jnp.einsum("bsd,vd->bsv", hidden.astype(F32), w_dense.astype(F32),
                     precision=HIGHEST)
    err = _rel_err(logits, ref)
    _check(err < BF16_TOL, f"head logits rel err {err:.3e} >= {BF16_TOL}")
    print(f"serving {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}, bf16 params + KV pool, "
          f"SparseLogitHead {head.weight.n_blocks_max} blocks of "
          f"{HEAD_BLOCK}x{HEAD_BLOCK} | {len(done)} requests (prompts "
          f"{sorted(set(lens))}) x {NEW_TOKENS} greedy tokens, all "
          f"'length', ids in range | head logits rel err vs HIGHEST dense "
          f"{err:.2e} (tol {BF16_TOL:g}) ok", flush=True)

    # static generate with the same head weights, densified, on request 0
    static, _ = generate(dict(params, lm_head=w_dense), cfg,
                         {"tokens": jnp.asarray(reqs[0].tokens)[None]},
                         sampling)
    same = np.asarray(static[0]) == np.asarray(done[0].tokens)
    agree = int(same.sum())
    first = int(np.argmin(same)) if not same.all() else "none"
    peak = jax.devices()[0].memory_stats().get("peak_bytes_in_use")
    print(f"cold-run observation, not a metric: engine and static "
          f"generate agree on {agree}/{NEW_TOKENS} tokens of request 0 "
          f"(first difference: {first}); backend compile "
          f"{sum(compile_s):.1f} s over {len(compile_s)} programs; "
          f"peak_bytes_in_use {peak}", flush=True)


def phase_partitioned(seed: int) -> None:
    """Partitioned SpMM fwd+grad on a real 4-device mesh, as 4x1 and 2x2,
    against the single-device compact result."""
    n_dev = len(jax.devices())
    _check(n_dev >= 4, f"--chips 4 needs 4 devices, JAX found {n_dev}")
    a, b, dc = spmm_operands(seed)
    single = _compile_with_kernel(spmm_fwd_vjp(plan_spmm_vjp(a)), a, b, dc)
    ref = single(a, b, dc)
    for shards, cols in ((4, 1), (2, 2)):
        mesh, _ = partition_mesh(shards, cols)
        _check(mesh is not None and mesh.devices.size == 4,
               f"no {shards}x{cols} mesh over 4 devices")
        train = plan_spmm_vjp(a, n_shards=shards, n_col_shards=cols)
        got = _compile_with_kernel(spmm_fwd_vjp(train), a, b, dc)(a, b, dc)
        spans = len(got[0].sharding.device_set)
        _check(spans == 4, f"{shards}x{cols}: output spans {spans} devices")
        errs = [_rel_err(x, r) for x, r in zip(got, ref)]
        _check(all(e < BF16_TOL for e in errs),
               f"{shards}x{cols}: fwd/dA/dB rel err {errs} >= {BF16_TOL}")
        print(f"partitioned spmm fwd+grad {shards}x{cols} (shard x col) "
              f"mesh: output over {spans} devices | rel err vs "
              f"single-device compact: fwd {errs[0]:.2e} dA {errs[1]:.2e} "
              f"dB {errs[2]:.2e} (tol {BF16_TOL:g}) ok", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the partitioned SpMM on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); there is no CPU fallback",
              file=sys.stderr)
        return 1
    compile_cache.enable()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_partitioned(args.seed)
    else:
        phase_spmm(args.seed)
        phase_spgemm(args.seed)
        phase_serving(args.seed)
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s",
          flush=True)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
