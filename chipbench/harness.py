"""The chip benchmark's run loop, driven by data.

A cell of ``BENCHMARK.json`` names a configuration (a device set, with its
file under ``chipbench/configs/``) and a traffic mix (``chipbench/traffic/
<name>.json``); every metric is read by ``chipbench/metrics/<name>.py``.
Nothing here names a cell, a configuration, a mix or a metric, so a new cell
is new files and a new entry.

One run: Predict on the real devices, seeded operands, a warm job of every
shape, then a window in which one closed-loop caller runs
``HGemms.execute`` back to back, each job timed from the call until C is on
the host.  After the window the C rows sampled from every job are compared
with a float64 host reference.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import sys
import tempfile
import threading
import time
import traceback

import numpy as np

from chipbench import reference as ref_mod
from chipbench.operands import make_operands

BENCH_FILE = "BENCHMARK.json"
WINDOW, JOB, SAMPLE = "chipbench.window", "chipbench.job", "chipbench.sample"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
PLAN_CALLS = 5


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- the cell, from files found by name --------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]      # the metric entries this cell reports
    per_layer: list[dict]
    root: pathlib.Path          # the checkout that holds BENCHMARK.json


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its configuration,
    its traffic and the metrics it reports."""
    bench = json.loads((root / BENCH_FILE).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {BENCH_FILE}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)],
                root=root)


def reader(root: pathlib.Path, metric: str):
    """``read(run) -> float | None`` of ``chipbench/metrics/<metric>.py``."""
    path = root / "chipbench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def job_order(traffic: dict, seed: int):
    """The closed loop's job shapes: the mix's jobs, each round in an order
    drawn from ``seed``, so every seed runs the same set of sizes."""
    shapes = [(j["m"], j["n"], j["k"]) for j in traffic["jobs"]]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 2])
    while True:
        for i in rng.permutation(len(shapes)):
            yield shapes[i]


# -- the devices -------------------------------------------------------------


def find_chips(cell: Cell):
    """The cell's chips and the host CPU.  Exits non-zero, before any result,
    where JAX finds no TPU or fewer chips than the cell asks for."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX found {platform!r}")
    chips = jax.devices("tpu")
    want = max(cell.chips, cell.config["chips"])
    if len(chips) < want:
        raise SystemExit(f"chipbench: {cell.name} needs {want} chip(s), JAX "
                         f"found {len(chips)}")
    return chips[:cell.config["chips"]], jax.devices("cpu")[0]


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


# -- Predict -----------------------------------------------------------------


def predict(config: dict, chips, cpu, *, interpret: bool = False):
    """Fit every device's time model (and each chip's host link) on the real
    devices, through the program's Predict layer."""
    import ml_dtypes

    from repro.core import (NO_COPY, CopyModel, DeviceProfile, Profiler,
                            device_runner, measure_bandwidth)
    from repro.core.hgemms import host_matmul, mxu_matmul

    bf16 = ml_dtypes.bfloat16
    prof = config["profile"]
    profiles = []
    if config["host_cpu"]:
        p = Profiler(device_runner(cpu, host_matmul, bf16),
                     repeats=prof["repeats"])
        p.run(prof["cpu_sizes"])
        profiles.append(DeviceProfile("host-cpu", "cpu", p.fit(), NO_COPY))
    kernel = functools.partial(mxu_matmul, interpret=interpret)
    for i, chip in enumerate(chips):
        p = Profiler(device_runner(chip, kernel, bf16),
                     repeats=prof["repeats"])
        p.run(prof["chip_sizes"])
        bw = measure_bandwidth(chip, nbytes=prof["bandwidth_bytes"])
        profiles.append(DeviceProfile(f"tpu{i}", "tpu", p.fit(),
                                      CopyModel(bw, dtype_size=2),
                                      align_m=config["align_m"]))
    return profiles


# -- one run -----------------------------------------------------------------


@dataclasses.dataclass
class Job:
    shape: tuple[int, int, int]
    seconds: float              # call to C on the host
    report: object              # the program's ExecutionReport
    rows: np.ndarray            # C at the sampled rows


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    chips: list
    profiles: list
    setup_s: float
    predict_s: float
    plan_s: list[float]         # HGemms.plan on an emptied plan cache
    jobs: list[Job]
    window_s: float             # window start to the last job's end
    trace: object = None        # chipbench.trace.Reduction of a traced run


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1      # the benchmark's own annotations
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def window(hg, cell: Cell, seed: int, seconds: float, operands: dict,
           rows: dict) -> tuple[list[Job], int, float]:
    """The measured window: whole jobs back to back, a job starting only
    while the window is open.  Returns (jobs, failed, seconds)."""
    import jax

    jobs, failed, order = [], 0, job_order(cell.traffic, seed)
    t_window = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW):
        while time.perf_counter() - t_window < seconds:
            shape = next(order)
            with jax.profiler.TraceAnnotation(JOB):
                t = time.perf_counter()
                try:
                    c, rep = hg.execute(*operands[shape])
                except Exception:
                    log(traceback.format_exc())
                    failed += 1
                    break
                dt = time.perf_counter() - t
            with jax.profiler.TraceAnnotation(SAMPLE):
                got = c[rows[shape]]
                del c
            jobs.append(Job(shape, dt, rep, got))
    return jobs, failed, time.perf_counter() - t_window


def run(cell: Cell, seed: int, seconds: float, trace: bool, *, chips, cpu,
        t_start: float, interpret: bool = False) -> dict:
    """One run of ``cell``: set-up, the measured window, the comparison and
    the metrics.  Returns the result line's object."""
    import jax

    from repro.core import HGemms

    cfg = cell.config
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        t = time.perf_counter()
        profiles = predict(cfg, chips, cpu, interpret=interpret)
        predict_s = time.perf_counter() - t
        bind = dict(zip((p.name for p in profiles),
                        ([cpu] if cfg["host_cpu"] else []) + chips))
        hg = HGemms(profiles, bind=bind, bus=cfg["bus"],
                    pipeline_chunks=cfg["pipeline_chunks"],
                    interpret=interpret)
        shapes = list(dict.fromkeys((j["m"], j["n"], j["k"])
                                    for j in cell.traffic["jobs"]))
        operands, rows, plan_s = {}, {}, []
        for shape in shapes:
            operands[shape] = make_operands(seed, *shape, chips[0])
            for _ in range(PLAN_CALLS):
                hg.plan_cache.invalidate()
                t = time.perf_counter()
                plan = hg.plan(*shape)
                plan_s.append(time.perf_counter() - t)
            parts = [(a.row0, a.m) for a in plan.adapted.assignments]
            rows[shape] = ref_mod.sample_rows(
                parts, seed, cfg["check"]["rows_per_partition"])
            log(f"[plan] {shape}: " + ", ".join(
                f"{d} rows {r0}..{r0 + m}" for d, (r0, m) in
                zip((a.device for a in plan.adapted.assignments), parts)))
            hg.execute(*operands[shape])        # warm: compiles every shape
        setup_s = time.perf_counter() - t_start
        log(f"[setup] {setup_s:.3f} s (predict {predict_s:.3f} s)")

        compiles = counter.n
        tdir = tempfile.TemporaryDirectory() if trace else None
        if trace:
            jax.profiler.start_trace(tdir.name,
                                     profiler_options=profile_options())
        jobs, failed, window_s = window(hg, cell, seed, seconds, operands,
                                        rows)
        if trace:
            jax.profiler.stop_trace()
        log(f"[window] {len(jobs)} job(s) in {window_s:.3f} s, {failed} "
            f"failed, {counter.n - compiles} compile(s) inside the window")
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in chips)
        del hg
        reduction = None
        if trace:
            from chipbench import trace as trace_mod
            reduction = trace_mod.reduce_dir(tdir.name, chips, WINDOW)
            tdir.cleanup()
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)

    # the comparison, once the window has closed and the program is freed
    err = 0.0
    for shape in shapes:
        want = ref_mod.reference(*operands[shape], rows[shape])
        for job in jobs:
            if job.shape == shape:
                err = max(err, ref_mod.max_rel_err(job.rows, want))
    limit = cfg["check"]["max_rel_err"]
    checks = {"max_rel_err": {"value": err, "limit": limit},
              "failed_jobs": {"value": failed, "limit": 0}}
    correct = bool(jobs) and failed == 0 and err <= limit

    record = Run(chips=chips, profiles=profiles, setup_s=setup_s,
                 predict_s=predict_s, plan_s=plan_s, jobs=jobs,
                 window_s=window_s, trace=reduction)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(cell.root, m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": chips[0].platform, "kind": chips[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(jobs) + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    result["checks"] = checks
    for name, c in checks.items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    return result
