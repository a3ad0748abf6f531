#!/usr/bin/env python3
"""Chip benchmark of POAS: one run of one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload i1.v5e1-cpu --seed 7 --seconds 51 \\
        --trace 0

Runs on the TPU chips of the machine it is started on, and exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for.  The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics from a traced window), ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``, each compared number
beside its limit (also the last lines on stderr).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache`` (a fixed
    path, so a later run of this checkout finds every program), unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; kept for every compile, since
    the GEMM kernels compile in under a second."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    from chipbench import harness

    cell = harness.load_cell(ROOT, args.workload)
    enable_compile_cache()
    chips, cpu = harness.find_chips(cell)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         chips=chips, cpu=cpu, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
