"""Serving launcher: batched generation against a (smoke or restored)
model — prefill + decode with sampling.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
      --batch 4 --prompt-len 16 --max-new 32 --temperature 0.8
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.ft import checkpoint as ckpt
from repro.launch import compile_cache
from repro.models import lm
from repro.serve import SamplingConfig, generate


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    # bf16 serving weights, made on the device: eager f32 init of a
    # published-width model does not fit one 16 GB chip
    params = jax.jit(lm.init_params, static_argnums=(0, 2))(
        cfg, key, jnp.bfloat16)
    if args.ckpt_dir:
        _, restored = ckpt.load(args.ckpt_dir, {"params": params})
        params = restored["params"]

    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    if cfg.n_patches:
        batch["vision_embeds"] = jax.random.normal(
            key, (args.batch, cfg.n_patches, cfg.d_model))
    if cfg.n_enc_layers:
        batch["enc_frames"] = jax.random.normal(
            key, (args.batch, cfg.enc_seq, cfg.d_model))

    sampling = SamplingConfig(temperature=args.temperature,
                              top_k=args.top_k,
                              max_new_tokens=args.max_new)
    t0 = time.perf_counter()
    tokens, entropies = generate(params, cfg, batch, sampling, key)
    dt = time.perf_counter() - t0
    n = tokens.shape[0] * tokens.shape[1]
    print(f"generated {tokens.shape} in {dt:.2f}s "
          f"({n / dt:.1f} tok/s incl. compile)")
    print("first row:", tokens[0].tolist())
    print("entropy trace:", [f"{e:.2f}" for e in entropies[:8]])
    return tokens


if __name__ == "__main__":
    main()
