#!/usr/bin/env python3
"""Chip smoke run: the paper's GEMM case study on TPU chips plus the host CPU.

Drives the POAS main path once at the paper's Table 3 input i1
(m = n = k = 30000, 27 TMAC): Predict (profiling runs on the real devices
and a host-link bandwidth micro-benchmark) -> Optimize -> Adapt -> Schedule
-> ``HGemms.execute`` -> ``OverlappedExecutor``/``StreamCore``.  A chip's
partition runs the Pallas MXU kernel on bf16 operands; the host CPU's runs
an f32-accumulating XLA matmul over the same values.  One cold execute
(which compiles) and one warm execute (which must compile nothing) are each
checked against a float64 host reference on sampled rows of every
partition.

    python3 chip_smoke.py [--seed N]        # one chip + the host CPU
    python3 chip_smoke.py --four-chips      # 4 chips + CPU, then 1 chip + CPU

It needs a TPU: anywhere else it exits non-zero and prints no result line.
The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

from benchmarks.common import PAPER_INPUTS  # noqa: E402

# Profiling sizes (squared GEMMs, paper §4.1.2): the largest chip size is a
# sixth of i1's chip partition, the CPU sizes stay within a few seconds.
CPU_SIZES = (1024, 2048, 3072, 4096)
CHIP_SIZES = (4096, 8192, 12288, 16384)
SAMPLE_ROWS = 16      # reference rows per partition, first and last included
REL_TOL = 1e-4        # max|C - ref| <= REL_TOL * max|ref|, per partition
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


class CompileCounter:
    """Counts traces and backend compiles in this process (a listener on
    JAX's monitoring events, which fire on whichever thread compiles)."""

    def __init__(self):
        self.n = 0
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event in COMPILE_EVENTS:
            with self._lock:
                self.n += 1


def operands(seed: int, m: int, n: int, k: int):
    """Seeded A and B as bf16.  Integers in [-16, 16) are exact in bf16 and
    every partial sum of i1 (|.| <= 256 * k < 2**24) is exact in f32, so
    each device's result is exact whatever its summation order."""
    import ml_dtypes
    rng = np.random.default_rng(seed)
    a = rng.integers(-16, 16, (m, k), dtype=np.int8).astype(ml_dtypes.bfloat16)
    b = rng.integers(-16, 16, (k, n), dtype=np.int8).astype(ml_dtypes.bfloat16)
    return a, b


def sampled_rows(row0: int, rows: int) -> np.ndarray:
    return np.unique(np.linspace(row0, row0 + rows - 1,
                                 min(SAMPLE_ROWS, rows)).astype(np.int64))


def reference(a, b, rows: np.ndarray, block: int = 2048) -> np.ndarray:
    """float64 host reference of C[rows], B converted a column block at a
    time."""
    a64 = a[rows].astype(np.float64)
    ref = np.empty((len(rows), b.shape[1]))
    for j in range(0, b.shape[1], block):
        ref[:, j:j + block] = a64 @ b[:, j:j + block].astype(np.float64)
    return ref


def predict(chips, cpu, *, cpu_sizes=CPU_SIZES, chip_sizes=CHIP_SIZES,
            interpret: bool = False):
    """Fit every device's LinearTimeModel (and the chips' CopyModel) from
    measurements on the real devices; nothing is assumed for the chip."""
    import functools

    import ml_dtypes

    from repro.core import (NO_COPY, CopyModel, DeviceProfile, Profiler,
                            device_runner, measure_bandwidth)
    from repro.core.hgemms import host_matmul, mxu_matmul

    bf16 = ml_dtypes.bfloat16
    prof = Profiler(device_runner(cpu, host_matmul, bf16), repeats=3)
    prof.run(cpu_sizes)
    fit = prof.fit()
    profiles = [DeviceProfile("host-cpu", "cpu", fit, NO_COPY)]
    log(f"[predict] host-cpu  a={fit.a:.4e} s/MAC  b={fit.b * 1e3:.3f} ms  "
        f"({1 / fit.a / 1e9:.1f} GMAC/s)  sizes {list(cpu_sizes)}")
    kernel = functools.partial(mxu_matmul, interpret=interpret)
    for i, chip in enumerate(chips):
        prof = Profiler(device_runner(chip, kernel, bf16), repeats=3)
        prof.run(chip_sizes)
        fit = prof.fit()
        bw = measure_bandwidth(chip)
        profiles.append(DeviceProfile(f"tpu{i}", "tpu", fit,
                                      CopyModel(bw, dtype_size=2), align_m=8))
        log(f"[predict] tpu{i}      a={fit.a:.4e} s/MAC  b={fit.b * 1e3:.3f} ms"
            f"  ({1 / fit.a / 1e12:.1f} TMAC/s)  sizes {list(chip_sizes)}  "
            f"host->device {bw / 1e9:.2f} GB/s")
    return profiles


def assert_mosaic(chip, m: int, n: int, k: int) -> None:
    """The chip partition's compiled program holds the Pallas kernel."""
    import jax
    import ml_dtypes
    from jax.sharding import SingleDeviceSharding

    from repro.core.hgemms import mxu_matmul

    on_chip = SingleDeviceSharding(chip)
    text = jax.jit(mxu_matmul).lower(
        jax.ShapeDtypeStruct((m, k), ml_dtypes.bfloat16, sharding=on_chip),
        jax.ShapeDtypeStruct((k, n), ml_dtypes.bfloat16, sharding=on_chip),
    ).compile().as_text()
    if "tpu_custom_call" not in text:
        fail(f"no tpu_custom_call in the {m}x{k}x{n} chip partition")


def run_case(label: str, profiles, devices, a, b, counter, *,
             interpret: bool = False) -> dict:
    """Plan, execute cold and warm, and check one co-execution."""
    from repro.core import HGemms

    m, k = a.shape
    n = b.shape[1]
    bind = dict(zip((p.name for p in profiles), devices))
    hg = HGemms(profiles, bind=bind, interpret=interpret)
    plan = hg.plan(m, n, k)
    planned = plan.schedule.timeline.makespan
    parts = [asg for asg in plan.adapted.assignments if asg.m]
    for asg in plan.adapted.assignments:
        log(f"[plan] {label}: {asg.device:9s} rows {asg.row0}..{asg.row0 + asg.m}"
            f"  share {asg.ops / (float(m) * n * k) * 100:.3f}%")
    log(f"[plan] {label}: planned makespan {planned:.4f} s")
    rows = {asg.device: sampled_rows(asg.row0, asg.m) for asg in parts}
    ref = reference(a, b, np.concatenate(list(rows.values())))
    refs, at = {}, 0
    for name, r in rows.items():
        refs[name] = ref[at:at + len(r)]
        at += len(r)

    walls = {}
    for phase in ("cold", "warm"):
        before = counter.n
        t0 = time.perf_counter()
        c, rep = hg.execute(a, b)
        walls[phase] = time.perf_counter() - t0
        compiles = counter.n - before
        measured = rep.measured.makespan
        log(f"[run] {label} {phase}: wall {walls[phase]:.4f} s  compiles "
            f"{compiles}  planned makespan {planned:.4f} s  measured "
            f"{measured:.4f} s  (measured/planned {measured / planned:.3f})")
        for ev in sorted(rep.measured.events, key=lambda e: (e.start, e.end)):
            log(f"[run] {label} {phase}:   {ev.device:9s} {ev.kind:8s} "
                f"{ev.start:9.4f} -> {ev.end:9.4f} s  ({ev.duration:.4f} s)")
        if phase == "warm" and compiles:
            fail(f"{label}: warm execute compiled {compiles} time(s)")
        for name, r in rows.items():
            err = float(np.max(np.abs(c[r] - refs[name])))
            scale = float(np.max(np.abs(refs[name])))
            placed = rep.placement[name]
            log(f"[check] {label} {phase}: {name:9s} {len(r)} rows  "
                f"max|C-ref| {err:.3e}  max|ref| {scale:.3e}  on "
                f"{sorted(map(str, placed))}")
            if not err <= REL_TOL * scale:
                fail(f"{label} {phase}: {name} error {err} > "
                     f"{REL_TOL} * {scale}")
            if placed != {bind[name]}:
                fail(f"{label} {phase}: {name} computed on {placed}, "
                     f"bound to {bind[name]}")
        del c, rep

    chip_parts = [asg for asg in parts if bind[asg.device].platform == "tpu"]
    for asg in chip_parts:
        assert_mosaic(bind[asg.device], asg.m, n, k)
    log(f"[check] {label}: tpu_custom_call in {len(chip_parts)} chip "
        f"partition(s)")
    for name, dev in bind.items():
        if dev.platform == "tpu":
            peak = dev.memory_stats()["peak_bytes_in_use"]
            log(f"[hbm] {label}: {name} peak_bytes_in_use {peak} "
                f"({peak / 2**30:.2f} GiB)")
    return walls


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="co-execute over 4 chips + the CPU and compare "
                         "with 1 chip + the CPU (no other phase)")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"needs a TPU; JAX found {devices[0].platform!r}")
    cpu = jax.devices("cpu")[0]     # the host CPU is a POAS device too
    chips = jax.devices("tpu")
    want = 4 if args.four_chips else 1
    if len(chips) < want:
        fail(f"--four-chips needs 4 chips, JAX found {len(chips)}")
    chips = chips[:want]
    log(f"[setup] jax {jax.__version__}  {devices[0].device_kind} x"
        f"{len(devices)}  host {cpu.device_kind}  compile cache {cache}")
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)

    profiles = predict(chips, cpu)
    m, n, k = PAPER_INPUTS["i1"]
    t0 = time.perf_counter()
    a, b = operands(args.seed, m, n, k)
    log(f"[setup] i1 operands {m}x{k} @ {k}x{n} bf16, seed {args.seed}, "
        f"made in {time.perf_counter() - t0:.2f} s")

    if args.four_chips:
        four = run_case("4chip+cpu", profiles, [cpu, *chips], a, b, counter)
        one = run_case("1chip+cpu", profiles[:2], [cpu, chips[0]], a, b,
                       counter)
        log(f"[compare] warm wall 1chip+cpu {one['warm']:.4f} s  4chip+cpu "
            f"{four['warm']:.4f} s  ({one['warm'] / four['warm']:.2f}x)")
    else:
        run_case("1chip+cpu", profiles, [cpu, *chips], a, b, counter)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
