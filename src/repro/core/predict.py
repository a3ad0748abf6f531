"""POAS phase 1 — *Predict*.

Builds per-device performance models.  Three sources, all producing the same
``TimeModel`` interface (paper §3.1 stresses modularity of the predictor):

1. ``fit_linear`` — least-squares linear regression of measured time over the
   op count (the paper's approach, §4.1.1); ``pool_profiles`` gives alike
   devices, fitted one by one, one model between them.
2. ``Profiler`` — the one-off profiling pass (paper §4.1.2): runs squared
   matmuls of growing size, measures, and regresses.  ``device_runner``
   times a kernel on a real ``jax.Device`` (the host CPU, a TPU chip) and
   ``measure_bandwidth`` times its host link; ``simulated_runner`` and
   ``measure_bandwidth_simulated`` reproduce the paper's testbed.
3. ``roofline_model`` — XLA-cost-analysis-driven predictor for TPU device
   groups (our hardware adaptation; see DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Callable, Hashable, NamedTuple, Sequence

import jax
import numpy as np

from .device_model import (CopyModel, DeviceProfile, LinearTimeModel,
                           RooflineTimeModel, NO_COPY)

# ---------------------------------------------------------------------------
# Regression
# ---------------------------------------------------------------------------


def fit_linear(ops: Sequence[float], seconds: Sequence[float],
               weights: Sequence[float] | None = None) -> LinearTimeModel:
    """Closed-form (weighted) least squares of t = a*ops + b, a>=0, b>=0."""
    x = np.asarray(ops, dtype=np.float64)
    y = np.asarray(seconds, dtype=np.float64)
    if weights is None:
        w = np.ones_like(x)
    else:
        w = np.asarray(weights, dtype=np.float64)
    sw = w.sum()
    mx, my = (w * x).sum() / sw, (w * y).sum() / sw
    vx = (w * (x - mx) ** 2).sum()
    if vx == 0.0:
        # Degenerate: single size — throughput-only model.  Clamp the slope
        # to the same positive floor as the main path: a zero-slope model
        # ("free compute at any size") would make every downstream solver
        # special-case it (solve_analytic holds zero-slope devices out of
        # the LP; the bisection would hand it the whole workload).
        a = max(float(my / mx) if mx else 0.0, 1e-18)
        return LinearTimeModel(a=a, b=0.0)
    a = float((w * (x - mx) * (y - my)).sum() / vx)
    a = max(a, 1e-18)
    b = max(float(my - a * mx), 0.0)
    return LinearTimeModel(a=a, b=b)


def relative_error(predicted: float, measured: float) -> float:
    """Paper §5.2: e = 100 * (v - v_pred) / v   (reported as |.| percent)."""
    if measured == 0.0:
        return 0.0
    return 100.0 * abs(measured - predicted) / measured


def rmse(errors_pct: Sequence[float]) -> float:
    e = np.asarray(errors_pct, dtype=np.float64)
    return float(np.sqrt(np.mean(e ** 2)))


class Pool(NamedTuple):
    """A group of alike devices given one model: the members' names, and
    the largest ``max / min - 1`` of their fitted seconds per MAC and of
    their host-link bandwidths, before pooling (the fits' noise)."""
    members: tuple[str, ...]
    spread: float


def _median_model(models: Sequence):
    """The models' field-wise median (a field equal in every model is kept
    as it is)."""
    fields = {}
    for f in dataclasses.fields(models[0]):
        vals = [getattr(m, f.name) for m in models]
        fields[f.name] = (vals[0] if all(v == vals[0] for v in vals)
                          else float(np.median(vals)))
    return type(models[0])(**fields)


def pool_profiles(devices: Sequence[DeviceProfile],
                  identity: Callable[[DeviceProfile], Hashable]
                  ) -> tuple[list[DeviceProfile], tuple[Pool, ...]]:
    """Give the devices of one hardware identity one time model and one
    host-link bandwidth, the members' field-wise medians, so that the
    solver sees alike devices as equal and not as apart by their fits'
    noise.  Names, order and every other field stay as given; a device
    alone in its identity is returned as it is.  Each pooled group of two
    or more is a ``Pool`` and a profiler span ``poas.predict.pool``."""
    groups: dict[Hashable, list[int]] = {}
    for i, d in enumerate(devices):
        key = (identity(d), type(d.compute), type(d.copy))
        groups.setdefault(key, []).append(i)
    out, pools = list(devices), []
    for idx in groups.values():
        if len(idx) < 2:
            continue
        members = [devices[i] for i in idx]
        names = tuple(d.name for d in members)
        bws = [d.copy.bandwidth_bytes_per_s for d in members]
        s_per_mac = [1.0 / d.effective_speed for d in members]
        spread = max(max(s_per_mac) / min(s_per_mac),
                     1.0 if math.isinf(bws[0]) else max(bws) / min(bws)) - 1.0
        with jax.profiler.TraceAnnotation("poas.predict.pool",
                                          kind=members[0].kind,
                                          members=" ".join(names),
                                          spread=spread):
            compute = _median_model([d.compute for d in members])
            copy = _median_model([d.copy for d in members])
            for i in idx:
                out[i] = dataclasses.replace(devices[i], compute=compute,
                                             copy=copy)
        pools.append(Pool(names, spread))
    return out, tuple(pools)


# ---------------------------------------------------------------------------
# Profiling (paper §4.1.2)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ProfileRecord:
    size: int           # squared matmul side
    ops: float          # size**3 MACs
    seconds: float


class Profiler:
    """Runs the paper's profiling pass: squared GEMMs, regress time over ops.

    ``runner(size) -> seconds`` abstracts the backend: real jitted matmul on
    the host, or a simulated device with synthetic noise.
    """

    def __init__(self, runner: Callable[[int], float], *, repeats: int = 5):
        self.runner = runner
        self.repeats = repeats
        self.records: list[ProfileRecord] = []

    @functools.partial(jax.profiler.annotate_function, name="poas.predict")
    def run(self, sizes: Sequence[int]) -> list[ProfileRecord]:
        self.records = []
        for s in sizes:
            ts = [self.runner(s) for _ in range(self.repeats)]
            self.records.append(
                ProfileRecord(size=s, ops=float(s) ** 3,
                              seconds=float(np.mean(ts))))
        return self.records

    def fit(self) -> LinearTimeModel:
        if not self.records:
            raise RuntimeError("run() the profiler before fit()")
        return fit_linear([r.ops for r in self.records],
                          [r.seconds for r in self.records])


def device_runner(device, matmul: Callable, dtype) -> Callable[[int], float]:
    """Measure ``matmul`` on a real ``jax.Device``: seeded square operands
    of ``dtype`` are committed to ``device`` and warmed once (compile + first
    run) per size; each call then times one run ending in
    ``block_until_ready``."""
    held: dict[int, tuple] = {}

    def run(size: int) -> float:
        if size not in held:
            held.clear()                  # keep one size's operands alive
            rng = np.random.default_rng(size)
            x, y = (rng.integers(-16, 16, (size, size), dtype=np.int8)
                    .astype(dtype) for _ in range(2))
            held[size] = jax.device_put((x, y), device)
            matmul(*held[size]).block_until_ready()
        t0 = time.perf_counter()
        matmul(*held[size]).block_until_ready()
        return time.perf_counter() - t0

    return run


def simulated_runner(profile: DeviceProfile, *, noise: float = 0.02,
                     seed: int = 0) -> Callable[[int], float]:
    """Synthesize profiling measurements from a ground-truth device profile.

    Multiplicative Gaussian noise models run-to-run variance (the paper's
    frequency-drift observation, §5.2).
    """
    rng = np.random.default_rng(seed)

    def run(size: int) -> float:
        t = profile.compute(float(size) ** 3)
        return max(t * (1.0 + noise * rng.standard_normal()), 1e-12)

    return run


def measure_bandwidth_simulated(profile: DeviceProfile, *, nbytes: int = 1 << 28,
                                noise: float = 0.01, seed: int = 1) -> float:
    """Paper's memory-bandwidth micro-benchmark, simulated."""
    import math
    if math.isinf(profile.copy.bandwidth_bytes_per_s):
        return float("inf")
    rng = np.random.default_rng(seed)
    t = nbytes / profile.copy.bandwidth_bytes_per_s
    t *= 1.0 + noise * rng.standard_normal()
    return nbytes / max(t, 1e-12)


@functools.partial(jax.profiler.annotate_function, name="poas.predict")
def measure_bandwidth(device, *, nbytes: int = 256 << 20,
                      repeats: int = 5) -> float:
    """Paper's memory-bandwidth micro-benchmark on a real host link: the
    median over ``repeats`` of a timed host->device ``device_put`` of
    ``nbytes``, after one warm-up transfer."""
    host = np.random.default_rng(0).integers(0, 256, nbytes, dtype=np.uint8)
    jax.device_put(host, device).block_until_ready()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        on_dev = jax.device_put(host, device)
        on_dev.block_until_ready()
        times.append(time.perf_counter() - t0)
        on_dev.delete()
    return nbytes / float(np.median(times))


# ---------------------------------------------------------------------------
# Profile persistence (paper stores profiling results in a text file)
# ---------------------------------------------------------------------------


def save_profiles(path: str, devices: Sequence[DeviceProfile]) -> None:
    import json
    import math
    rows = []
    for d in devices:
        row = {"name": d.name, "kind": d.kind, "align_m": d.align_m,
               "align_k": d.align_k, "cache_bytes": d.cache_bytes,
               "pipeline_chunks": d.pipeline_chunks}
        if isinstance(d.compute, LinearTimeModel):
            row["model"] = {"type": "linear", "a": d.compute.a, "b": d.compute.b}
        else:
            row["model"] = {"type": "roofline",
                            "peak_ops_per_s": d.compute.peak_ops_per_s,
                            "hbm_bytes_per_s": d.compute.hbm_bytes_per_s,
                            "bytes_per_op": d.compute.bytes_per_op,
                            "overhead_s": d.compute.overhead_s}
        row["copy"] = {"bw": (None if math.isinf(d.copy.bandwidth_bytes_per_s)
                              else d.copy.bandwidth_bytes_per_s),
                       "dtype_size": d.copy.dtype_size,
                       "latency_s": d.copy.latency_s}
        rows.append(row)
    with open(path, "w") as f:
        json.dump(rows, f, indent=2)


def load_profiles(path: str) -> list[DeviceProfile]:
    import json
    import math
    with open(path) as f:
        rows = json.load(f)
    out = []
    for row in rows:
        m = row["model"]
        if m["type"] == "linear":
            compute = LinearTimeModel(a=m["a"], b=m["b"])
        else:
            compute = RooflineTimeModel(
                peak_ops_per_s=m["peak_ops_per_s"],
                hbm_bytes_per_s=m["hbm_bytes_per_s"],
                bytes_per_op=m["bytes_per_op"], overhead_s=m["overhead_s"])
        c = row["copy"]
        copy = (NO_COPY if c["bw"] is None else
                CopyModel(c["bw"], dtype_size=c["dtype_size"],
                          latency_s=c["latency_s"]))
        out.append(DeviceProfile(row["name"], row["kind"], compute, copy,
                                 align_m=row["align_m"], align_k=row["align_k"],
                                 cache_bytes=row["cache_bytes"],
                                 pipeline_chunks=row.get("pipeline_chunks", 1)))
    return out
