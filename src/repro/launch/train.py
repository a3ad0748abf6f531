"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-12b --tiny \
        --steps 200 --batch 8 --seq 128 [--ckpt-dir /tmp/ckpt] [--resume]

Composes the full stack: config → Model → AdamW → synthetic data pipeline →
fault-tolerant runner (checkpoint/restart) → POAS hetero-DP split when more
than one pod profile is given.  On this container run with ``--tiny``; on a
TPU fleet drop the flag and launch one process per host.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config, get_tiny_config
from ..data.pipeline import DataConfig, Prefetcher, SyntheticLM
from ..distributed.elastic import FaultTolerantRunner, RunnerConfig
from ..models import Model
from ..training.optim import AdamW, cosine_schedule
from ..training.step import make_train_step
from .compile_cache import enable_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-12b", choices=ARCH_IDS)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_tiny_config(args.arch) if args.tiny else get_config(args.arch)
    model = Model(cfg)
    opt = AdamW(learning_rate=cosine_schedule(args.lr, warmup=20,
                                              total=args.steps),
                state_dtype=jnp.float32 if args.tiny else jnp.bfloat16)

    params = model.init(jax.random.PRNGKey(args.seed))
    state = {"params": params, "opt": opt.init(params)}
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M "
          f"steps={args.steps} batch={args.batch}x{args.seq}")

    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=args.seed,
        embed_dim=cfg.d_model if cfg.frontend != "none" else 0))
    step_fn = jax.jit(make_train_step(model, opt))

    def wrapped(state, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        new_state, metrics = step_fn(state, batch)
        return new_state, metrics

    losses = []

    def on_metrics(step, metrics):
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == 1:
            print(f"step {step:5d}  loss {losses[-1]:.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"lr {float(metrics['lr']):.2e}", flush=True)

    start_step = 0
    if args.ckpt_dir:
        runner = FaultTolerantRunner(
            RunnerConfig(checkpoint_dir=args.ckpt_dir,
                         checkpoint_every=args.ckpt_every),
            step_fn=wrapped, state=state)
        if args.resume and runner.restore_latest():
            print(f"resumed from step {runner.step}")
        t0 = time.time()
        runner.run(Prefetcher(data.stream(runner.step)), args.steps,
                   on_metrics=on_metrics)
        dt = time.time() - t0
    else:
        t0 = time.time()
        pf = Prefetcher(data.stream(0))
        for step in range(1, args.steps + 1):
            state, metrics = wrapped(state, next(pf))
            on_metrics(step, metrics)
        dt = time.time() - t0

    if len(losses) >= 20:
        first = np.mean(losses[:5])
        last = np.mean(losses[-5:])
        print(f"loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'}) "
              f"in {dt:.0f}s ({dt/max(len(losses),1)*1e3:.0f} ms/step)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
