"""hgemms — the paper's DS-POAS for heterogeneous GEMM (§4).

Splits an (m, n, k) GEMM's rows across heterogeneous devices per the POAS
plan and executes the partitions through the overlapped co-execution runtime
(``core.executor``): one thread per device, input/output copies serialized
on the shared bus in the planned priority order, compute overlapping other
devices' copies.

``bind`` maps each POAS device profile to a ``jax.Device``.  A partition's
``copy_in`` commits its A row-slice and B to that device
(``jax.device_put``), ``compute`` runs there and ``copy_out`` reads its C
rows back to the host; every stage ends in ``block_until_ready``, so the
measured intervals are the transfers and the compute themselves, not their
enqueue.  A device with no planned copy stage (the host CPU) computes in
place.  Inside the stages, ``phase``s split the work the stage interval
holds: a copy_out's ``d2h`` read and its ``store`` into C; an in-place
compute's operand ``put``, its ``kernel`` and its ``store``.  A profile
with a host link (an accelerator such as a TPU chip) runs the Pallas MXU
kernel (``repro.kernels.matmul``), its C rows laid out row-major on the
device so that their store into C is a straight copy; a profile without
one runs an f32-accumulating XLA matmul.  Operands cross the link in the
dtype the caller hands in — bf16 for a chip, the 2-byte dtype its
``CopyModel`` prices — and C always accumulates and returns in f32.

A store of rows into C (``_store_rows``) is spread over host threads by its
size: C is a fresh ``np.zeros``, so the store is the first write to each of
its pages, and page faults on separate row blocks are taken in parallel
(numpy releases the GIL for the copy).  A store of less than
``_STORE_BLOCK_FLOOR`` bytes stays one copy on the calling thread.

Without ``bind`` every partition runs the XLA matmul on the host CPU device:
the simulated testbeds (``paper_mach1``/``mach2``) keep real numerics while
their per-device *times* come from the device models.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from ..kernels.ops import matmul as pallas_matmul
from .adapt import GemmPlan
from .bus import BusTopology
from .device_model import DeviceProfile, with_pipeline
from .domain import PlanCache
from .executor import DeviceTask, OverlappedExecutor, phase
from .framework import GemmWorkload, POASPlan, make_gemm_poas
from .predict import Pool, pool_profiles
from .schedule import DynamicScheduler, Timeline, simulate_timeline


@jax.jit
def host_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """The host CPU's kernel: XLA matmul accumulating in f32.  Over bf16
    operands it equals an f32 matmul of the same values (bf16 products are
    exact in f32)."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def mxu_matmul(a: jax.Array, b: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """An accelerator's kernel: the Pallas MXU matmul (``kernels.matmul``,
    jitted at module level) with f32 accumulation and output."""
    return pallas_matmul(a, b, out_dtype=jnp.float32, interpret=interpret)


def row_major_matmul(dev: jax.Device, interpret: bool = False) -> Callable:
    """``mxu_matmul`` as one program pinned to ``dev`` whose C rows come
    out row-major, the layout of the host C they are stored into.

    Left to itself, the TPU compiler lays an (m, n) result out in whichever
    order pads its (8, 128) tiles least: column-major when ``m`` pads less
    than ``n``, as for most partition shares.  The host would then read
    F-ordered rows and store them into C with a transposing copy.  Built
    once per device, so a warm ``execute`` traces nothing; the module keeps
    the kernel's name, ``jit_matmul``."""
    return _row_major(mxu_matmul, dev, interpret)


@functools.lru_cache(maxsize=None)
def _row_major(kernel: Callable, dev: jax.Device, interpret: bool) -> Callable:
    # keyed by the kernel too: the program runs what ``mxu_matmul`` names
    # when it is asked for, as the unpinned call did
    def matmul(a, b):
        return kernel(a, b, interpret=interpret)

    return jax.jit(matmul, out_shardings=Format(
        Layout(major_to_minor=(0, 1)), SingleDeviceSharding(dev)))


# A store of rows into C takes one host thread per ``_STORE_BLOCK_FLOOR``
# bytes, up to the usable CPUs and ``_STORE_THREADS_CAP``: the smallest
# thread count whose store of a chip share's rows into a fresh C came within
# 10% of the fastest (PERF.md §6, PR 18).
_STORE_BLOCK_FLOOR = 64 << 20
_STORE_THREADS_CAP = 8
_store_pool: ThreadPoolExecutor | None = None   # made by the first store
_store_pool_lock = threading.Lock()


def _store_threads(rows: int, row_bytes: int) -> int:
    """How many host threads a store of ``rows`` rows into C takes."""
    return max(1, min(_STORE_THREADS_CAP, len(os.sched_getaffinity(0)), rows,
                      -(-rows * row_bytes // _STORE_BLOCK_FLOOR)))


def _store_executor() -> ThreadPoolExecutor:
    global _store_pool
    with _store_pool_lock:
        if _store_pool is None:
            _store_pool = ThreadPoolExecutor(_STORE_THREADS_CAP,
                                             thread_name_prefix="poas-store")
        return _store_pool


def _copy_rows(c: np.ndarray, r0: int, rows: np.ndarray) -> None:
    c[r0:r0 + len(rows)] = rows


def _store_rows(c: np.ndarray, r0: int, rows: np.ndarray) -> int:
    """``c[r0:r0 + len(rows)] = rows``, as contiguous row blocks copied at
    once by the module's store pool (``_store_threads``).  Waits for every
    block and raises the first block's error; returns the threads used."""
    threads = _store_threads(len(rows), c.shape[1] * c.itemsize)
    if threads == 1:
        _copy_rows(c, r0, rows)
        return 1
    bounds = [len(rows) * i // threads for i in range(threads + 1)]
    pool = _store_executor()
    futures = [pool.submit(_copy_rows, c, r0 + i0, rows[i0:i1])
               for i0, i1 in zip(bounds, bounds[1:])]
    wait(futures)
    for f in futures:
        f.result()
    return threads


@dataclasses.dataclass
class ExecutionReport:
    plan: POASPlan
    timeline: Timeline
    predicted_makespan: float
    simulated_makespan: float      # from device models (+noise if asked)
    wall_seconds: float            # actual host wall time of the partitions
    standalone: dict[str, float]   # predicted time if each device ran alone
    measured: Timeline | None = None   # executor's real per-stage intervals
    # profile name -> the jax devices its computed C blocks lived on
    placement: dict[str, set] = dataclasses.field(default_factory=dict)
    pools: tuple[Pool, ...] = ()   # HGemms.pools: the alike devices pooled

    @property
    def speedups(self) -> dict[str, float]:
        return {name: t / self.simulated_makespan
                for name, t in self.standalone.items()}


class HGemms:
    """Heterogeneous GEMM scheduler (paper §4).

    ``bind`` maps every profile name to the ``jax.Device`` its partitions
    run on; ``interpret`` runs the accelerators' Pallas kernel in interpret
    mode (CPU tests bind the CPU device in the chip's place).  Bound
    profiles of one hardware identity (``_hardware``: the same kind, bound
    to the same kind of ``jax.Device``, with the same alignment, copy dtype
    and host link) are pooled into one model (``predict.pool_profiles``),
    so a plan over alike chips does not follow their fits' noise;
    ``pools`` holds each group of two or more.  Unbound (simulated)
    profiles are planned as given."""

    def __init__(self, devices: Sequence[DeviceProfile], *,
                 bus: str | BusTopology = "serialized",
                 dynamic: bool = False, cache: bool = True,
                 pipeline_chunks: int | None = None,
                 bind: Mapping[str, jax.Device] | None = None,
                 interpret: bool = False):
        self.devices = list(devices)
        if pipeline_chunks is not None:
            # chunked pipelined copies (DESIGN.md §4): the adapt phase maps
            # each copying device's chunk count to row-chunks of its A slice
            self.devices = with_pipeline(self.devices, pipeline_chunks)
        if bind is not None:
            names = {d.name for d in self.devices}
            if set(bind) != names:
                raise ValueError(f"bind must name exactly the profiles "
                                 f"{sorted(names)}, got {sorted(bind)}")
        self.bind = dict(bind) if bind is not None else None
        self.interpret = interpret
        self.pools: tuple[Pool, ...] = ()
        if self.bind is not None:
            self.devices, self.pools = pool_profiles(self.devices,
                                                     self._hardware)
        self.poas, self.dyn = make_gemm_poas(self.devices, bus=bus,
                                             dynamic=dynamic, cache=cache)
        self.bus = self.poas.domain.bus
        self.topology = self.poas.domain.topology

    @property
    def plan_cache(self) -> PlanCache | None:
        return self.poas.cache

    # -- planning ----------------------------------------------------------

    def plan(self, m: int, n: int, k: int) -> POASPlan:
        return self.poas.plan(GemmWorkload(m=m, n=n, k=k))

    # -- execution ---------------------------------------------------------

    def _hardware(self, prof: DeviceProfile) -> tuple:
        """What makes two bound profiles the same hardware."""
        return (prof.kind, self.bind[prof.name].device_kind, prof.align_m,
                prof.align_k, prof.copy.dtype_size,
                not math.isinf(prof.copy.bandwidth_bytes_per_s))

    def _target(self, prof: DeviceProfile) -> tuple[jax.Device, Callable]:
        """The device a profile's partitions run on, and their kernel."""
        if self.bind is None:
            return jax.devices("cpu")[0], host_matmul
        dev = self.bind[prof.name]
        if math.isinf(prof.copy.bandwidth_bytes_per_s):
            return dev, host_matmul
        return dev, row_major_matmul(dev, self.interpret)

    def _partition_tasks(self, a: np.ndarray, b: np.ndarray, c: np.ndarray,
                         gplan: GemmPlan, planned: Timeline,
                         placement: dict[str, set]) -> list[DeviceTask]:
        """One ``DeviceTask`` per device with work; stages mirror the planned
        timeline (devices with no planned copy event compute in place).
        Devices with pipelined row chunks get per-chunk stage lists so the
        executor streams them — chunk 1's matmul really overlaps chunk 2's
        copy, the overlap the chunked plan prices.  The shared B panel
        rides input chunk 0, exactly how the engine prices it."""
        planned_kinds = {(e.device, e.kind) for e in planned.events}
        row_bytes = c.shape[1] * c.itemsize
        tasks: list[DeviceTask] = []
        for prof, asg in zip(self.devices, gplan.assignments):
            if asg.m == 0:
                continue
            dev, kernel = self._target(prof)
            has_in = (prof.name, "copy_in") in planned_kinds
            has_out = (prof.name, "copy_out") in planned_kinds
            if has_in and len(asg.chunk_rows) > 1:
                chunks = [(r0, r0 + rr) for r0, rr in
                          zip(asg.chunk_offsets(), asg.chunk_rows)]
            else:
                chunks = [(asg.row0, asg.row0 + asg.m)]
            last = len(chunks) - 1
            placed = placement[prof.name] = set()
            state: dict = {}

            def copy_in(j, r0, r1, dev=dev, state=state):
                # host -> device: A row-slice (+ the full B with chunk 0)
                if j == 0:
                    state["b"] = jax.device_put(b, dev)
                state["a", j] = jax.device_put(a[r0:r1], dev)
                jax.block_until_ready((state["a", j], state["b"]))

            def compute(j, r0, r1, kernel=kernel, placed=placed, state=state,
                        copy_in=copy_in, has_in=has_in, has_out=has_out,
                        last=last):
                if not has_in:            # no-copy device computes in place
                    with phase("put", bytes=b.nbytes
                               + (r1 - r0) * a.shape[1] * a.itemsize):
                        copy_in(j, r0, r1)
                with phase("kernel"):
                    out = kernel(state.pop(("a", j)), state["b"])
                    out.block_until_ready()
                placed.update(out.devices())
                if j == last:             # drop B: frees device memory early
                    del state["b"]
                if has_out:
                    state["c", j] = out
                else:
                    with phase("store", bytes=(r1 - r0) * row_bytes,
                               threads=_store_threads(r1 - r0, row_bytes)):
                        _store_rows(c, r0, np.asarray(out))

            def copy_out(j, r0, r1, state=state):
                # device -> host; dropping the array frees its device memory
                nbytes = (r1 - r0) * row_bytes
                with phase("d2h", bytes=nbytes):
                    host = np.asarray(state.pop(("c", j)))
                with phase("store", bytes=nbytes,
                           row_major=host.flags.c_contiguous,
                           threads=_store_threads(r1 - r0, row_bytes)):
                    _store_rows(c, r0, host)
                    del host

            stages = [[functools.partial(fn, j, r0, r1)
                       for j, (r0, r1) in enumerate(chunks)]
                      for fn in (copy_in, compute, copy_out)]
            if len(chunks) > 1:
                tasks.append(DeviceTask(
                    device=prof.name, copy_in=None, compute=None,
                    copy_out=None, copy_in_chunks=stages[0],
                    compute_chunks=stages[1],
                    copy_out_chunks=stages[2] if has_out else None))
            else:
                tasks.append(DeviceTask(
                    device=prof.name,
                    copy_in=stages[0][0] if has_in else None,
                    compute=stages[1][0],
                    copy_out=stages[2][0] if has_out else None))
        return tasks

    @functools.partial(jax.profiler.annotate_function, name="poas.execute")
    def execute(self, a: np.ndarray, b: np.ndarray, *,
                noise: float = 0.0, seed: int = 0,
                plan: POASPlan | None = None) -> tuple[np.ndarray, ExecutionReport]:
        """Run the co-executed GEMM.  Returns (C, report); C is f32 (or
        wider, for wider operands).

        Partitions run concurrently through ``OverlappedExecutor`` on their
        bound devices (real numerics, real overlap, bus order from the
        plan); ``report.measured`` holds the executor's measured stage
        intervals, each with its ``phases`` and its ticket ``wait`` (the
        call is the profiler span ``poas.execute``).  The report's
        simulated times come from the device models (optionally noised),
        so an unbound simulated testbed reproduces the paper's timing
        behaviour deterministically on one CPU.
        """
        m, k = a.shape
        k2, n = b.shape
        assert k == k2, (a.shape, b.shape)
        p = plan or self.plan(m, n, k)
        gplan: GemmPlan = p.adapted

        rng = np.random.default_rng(seed)
        c = np.zeros((m, n), dtype=np.result_type(a.dtype, b.dtype,
                                                  np.float32))
        planned = p.schedule.timeline
        placement: dict[str, set] = {}
        tasks = self._partition_tasks(a, b, c, gplan, planned, placement)

        t0 = time.perf_counter()
        measured = OverlappedExecutor(self.devices, planned).run(tasks)
        wall = time.perf_counter() - t0

        device_times: dict[str, float] = {}
        ops_list = []
        for di, (dev, asg) in enumerate(zip(self.devices, gplan.assignments)):
            ops_list.append(asg.ops)
            if asg.m == 0:
                device_times[dev.name] = 0.0
                continue
            t = dev.total_time(asg.ops, n, k)
            if noise:
                t *= 1.0 + noise * rng.standard_normal()
            device_times[dev.name] = t
            if self.dyn is not None:
                self.dyn.observe(di, asg.ops,
                                 dev.compute(asg.ops) * (1.0 + (noise * rng.standard_normal() if noise else 0.0)))
        tl = simulate_timeline(self.devices, ops_list, n, k,
                               topology=self.topology,
                               chunks=[max(1, len(a.chunk_rows))
                                       for a in gplan.assignments])
        standalone = {d.name: d.total_time(float(m) * n * k, n, k)
                      for d in self.devices}
        rep = ExecutionReport(
            plan=p, timeline=tl,
            predicted_makespan=p.schedule.timeline.makespan,
            simulated_makespan=max(tl.makespan,
                                   max(device_times.values(), default=0.0)),
            wall_seconds=wall, standalone=standalone,
            measured=measured, placement=placement, pools=self.pools)
        return c, rep

    # -- prediction accuracy experiment (paper §5.2) ------------------------

    def prediction_errors(self, m: int, n: int, k: int, *,
                          noise: float = 0.03, seed: int = 0) -> dict[str, dict[str, float]]:
        """Per-device compute/copy/global relative error vs a noisy 'measured'
        run — reproduces Table 4's structure on the simulated testbed."""
        from .predict import relative_error
        p = self.plan(m, n, k)
        gplan: GemmPlan = p.adapted
        rng = np.random.default_rng(seed)
        out: dict[str, dict[str, float]] = {}
        for dev, asg in zip(self.devices, gplan.assignments):
            if asg.m == 0:
                continue
            pred_c = dev.compute(asg.ops)
            pred_y = dev.copy(asg.ops, n, k)
            meas_c = pred_c * (1.0 + noise * rng.standard_normal())
            meas_y = pred_y * (1.0 + 0.3 * noise * rng.standard_normal())
            out[dev.name] = {
                "compute": relative_error(pred_c, meas_c),
                "copy": relative_error(pred_y, meas_y) if pred_y else 0.0,
                "global": relative_error(pred_c + pred_y, meas_c + meas_y),
            }
        return out
