#!/usr/bin/env python3
"""The readings that the limit of ``max_rel_err`` is set from.

    python3 chipbench/control.py --workload i1.v5e1-cpu --seeds 1 2 3 ...

For each seed, in one process on the cell's chips: the cell's operands, one
job through the timed path (``HGemms.execute`` with the cell's devices and
plan) and the comparison a run makes, at the same sampled rows:

- ``program``: the timed path's C, the sound reading (the lower one);
- ``fp8``: the control, the reference put in the program's place with its
  operands rounded to float8 (e4m3), the precision below the stated bf16,
  accumulated in float32 on the chip;
- ``bf16_out``: the program's C rounded to bfloat16, as a copy path that
  returned C in bf16 would give.

The benchmark's own runs do not run this.  One JSON line per seed, then a
summary line with the largest ``program`` and the smallest control reading.
"""
import argparse
import json
import pathlib
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


@jax.jit
def _fp8_matmul(x, y):
    lo = jnp.float8_e4m3fn
    return jnp.matmul(x.astype(lo).astype(jnp.float32),
                      y.astype(lo).astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def fp8_rows(a_rows, b, device):
    """C rows from operands rounded to float8 e4m3, f32 accumulate, on
    ``device``."""
    return jax.device_get(_fp8_matmul(*jax.device_put((a_rows, b), device)))


def bf16_rows(c_rows):
    import ml_dtypes

    return c_rows.astype(ml_dtypes.bfloat16).astype("float32")


def readings(a, b, c_rows, rows, device) -> dict:
    """The program's reading and the two controls' at ``rows``."""
    from chipbench import reference

    want = reference.reference(a, b, rows)
    return {"program": reference.max_rel_err(c_rows, want),
            "fp8": reference.max_rel_err(fp8_rows(a[rows], b, device), want),
            "bf16_out": reference.max_rel_err(bf16_rows(c_rows), want)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from chipbench import harness, reference
    from chipbench.operands import make_operands
    from repro.core import HGemms

    cell = harness.load_cell(ROOT, args.workload)
    cfg = cell.config
    chips, cpu = harness.find_chips(cell)
    profiles = harness.predict(cfg, chips, cpu)
    bind = dict(zip((p.name for p in profiles),
                    ([cpu] if cfg["host_cpu"] else []) + chips))
    hg = HGemms(profiles, bind=bind, bus=cfg["bus"],
                pipeline_chunks=cfg["pipeline_chunks"])
    shape = next(harness.job_order(cell.traffic, 0))
    plan = hg.plan(*shape)
    parts = [(asg.row0, asg.m) for asg in plan.adapted.assignments]
    harness.log(f"[control] {cell.name} {shape} partitions {parts}")
    worst = {"program": 0.0, "fp8": float("inf"), "bf16_out": float("inf")}
    for seed in args.seeds:
        a, b = make_operands(seed, *shape, chips[0])
        rows = reference.sample_rows(parts, seed,
                                     cfg["check"]["rows_per_partition"])
        t = time.perf_counter()
        c, _ = hg.execute(a, b)
        job_s = time.perf_counter() - t
        c_rows = c[rows]
        del c
        r = readings(a, b, c_rows, rows, chips[0])
        worst["program"] = max(worst["program"], r["program"])
        worst["fp8"] = min(worst["fp8"], r["fp8"])
        worst["bf16_out"] = min(worst["bf16_out"], r["bf16_out"])
        print(json.dumps({"seed": seed, "rows": len(rows), "job_s": job_s,
                          **r}), flush=True)
    print(json.dumps({"workload": cell.name, "seeds": len(args.seeds),
                      "lower_program_max": worst["program"],
                      "upper_fp8_min": worst["fp8"],
                      "bf16_out_min": worst["bf16_out"],
                      "device": chips[0].device_kind,
                      "seconds": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
