"""Serving driver: batched generation with POAS dispatch.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-12b --tiny \
        --requests 16 --max-new 8 [--groups 2]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import ARCH_IDS, get_config, get_tiny_config
from ..core.device_model import DeviceProfile, LinearTimeModel, NO_COPY
from ..models import Model
from ..serving.engine import PoasDispatcher, Request, ServingEngine
from .compile_cache import enable_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-12b", choices=ARCH_IDS)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--groups", type=int, default=2,
                    help="simulated replica groups for POAS dispatch")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_tiny_config(args.arch) if args.tiny else get_config(args.arch)
    if cfg.frontend != "none":
        print(f"{cfg.name}: stub-frontend arch — serving demo uses token "
              "inputs; pick a text arch")
        return 0
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    engine = ServingEngine(model, params)

    rng = np.random.default_rng(args.seed)
    reqs = [Request(uid=i,
                    tokens=rng.integers(1, cfg.vocab_size,
                                        int(rng.integers(4, 32))),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]

    groups = [DeviceProfile(f"group{i}", "tpu-group",
                            LinearTimeModel(a=(1 + i) * 1e-6, b=1e-3),
                            NO_COPY)
              for i in range(args.groups)]
    disp = PoasDispatcher(groups)
    buckets = disp.split(reqs)
    shares = (disp.last_plan.optimize.shares() if disp.last_plan
              else [0.0] * len(groups))
    print(f"dispatch[{disp.domain.name}]:", [len(b) for b in buckets],
          f"shares {[f'{s:.2f}' for s in shares]} "
          f"predicted makespan {disp.predicted_makespan(buckets)*1e3:.2f}ms")
    disp.split(reqs)   # identical batch geometry -> PlanCache hit
    print(f"plan cache: {disp.poas.cache.stats()}")

    t0 = time.perf_counter()
    done = []
    for bucket in buckets:
        done += engine.generate(bucket)
    dt = time.perf_counter() - t0
    total = sum(len(c.tokens) for c in done)
    print(f"{len(done)} completions, {total} tokens in {dt:.2f}s "
          f"({total/dt:.0f} tok/s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
