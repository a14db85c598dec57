"""Training launcher: --arch <id> [--smoke] with checkpoints, resume,
straggler monitoring and deterministic data.

On real hardware this process is started once per host (jax.distributed
initializes from the cluster env); in this container it drives the
single-process path with the same code.  The dry-run (launch/dryrun.py) is
the multi-pod compile proof; this launcher is the runnable loop.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \
      --steps 20 --ckpt-dir /tmp/ckpt --ckpt-every 10
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.data import DataConfig, synth_batch
from repro.ft import checkpoint as ckpt
from repro.ft.straggler import StragglerMonitor, StepTimer
from repro.launch import compile_cache
from repro.models import lm
from repro.train import OptimizerConfig, init_opt_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--micro-batches", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    ocfg = OptimizerConfig(peak_lr=args.lr, warmup_steps=5,
                           total_steps=max(args.steps, 10))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.global_batch, seed=args.seed)

    extra = {}
    if cfg.n_enc_layers:
        extra["enc_frames"] = (args.global_batch, cfg.enc_seq, cfg.d_model)
    if cfg.n_patches:
        extra["vision_embeds"] = (args.global_batch, cfg.n_patches,
                                  cfg.d_model)

    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = init_opt_state(ocfg, params)
    start = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        start, restored = ckpt.load(args.ckpt_dir,
                                    {"params": params, "opt": opt})
        params, opt = restored["params"], restored["opt"]
        print(f"resumed from step {start}")

    # sparse-MLP configs: one host-side symbolic pass; the jitted step
    # closes over the shared fwd+bwd plan (None for dense configs)
    step_fn = jax.jit(make_train_step(cfg, ocfg, args.micro_batches,
                                      mlp_plan=lm.sparse_mlp_plan(params)))
    monitor = StragglerMonitor()
    host = f"host{jax.process_index()}"

    for step in range(start, args.steps):
        batch = synth_batch(dcfg, step, extra)
        with StepTimer(monitor, host):
            params, opt, metrics = step_fn(params, opt, batch)
        slow = monitor.check()
        if slow:
            print(f"[straggler] flagged: {slow}")
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e}", flush=True)
        if args.ckpt_dir and ((step + 1) % args.ckpt_every == 0
                              or step == args.steps - 1):
            path = ckpt.save(args.ckpt_dir, step + 1,
                             {"params": params, "opt": opt})
            ckpt.garbage_collect(args.ckpt_dir, keep=3)
            print(f"checkpointed → {path}")
    return params


if __name__ == "__main__":
    main()
