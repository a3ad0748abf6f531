"""The readers of the program's phases and ticket waits: their values on a
synthetic run, nothing from a program that records no phases, the cells
each one lists, and the bus wait of two chips sharing one serialized link,
read in process."""
import dataclasses
import json
import pathlib
import sys
import types

import jax
import ml_dtypes
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402
from repro.core import (BusEvent, CopyModel, DeviceProfile, HGemms,  # noqa: E402
                        LinearTimeModel, Timeline)

READERS = ("chip_d2h_s", "chip_store_s", "cpu_put_s", "bus_wait_s")


def _profiles():
    return [types.SimpleNamespace(name="host-cpu", kind="cpu"),
            types.SimpleNamespace(name="tpu0", kind="tpu"),
            types.SimpleNamespace(name="tpu1", kind="tpu")]


def _job(events):
    report = types.SimpleNamespace(measured=Timeline(events))
    return harness.Job(shape=(8, 8, 8), seconds=1.0, report=report,
                       rows=np.zeros(0))


def _run(jobs):
    return harness.Run(chips=[], profiles=_profiles(), setup_s=0.0,
                       predict_s=0.0, plan_s=[], jobs=jobs, window_s=1.0)


def _synthetic_job(scale):
    s = scale
    return _job([
        BusEvent("host-cpu", "compute", 0.0, 5 * s, None,
                 phases=(("put", 0.0, 3 * s), ("kernel", 3 * s, 4 * s),
                         ("store", 4 * s, 4.5 * s))),
        BusEvent("tpu0", "copy_in", 0.0, 1 * s, "bus", wait=0.0),
        BusEvent("tpu1", "copy_in", 1 * s, 2 * s, "bus", wait=1 * s),
        BusEvent("tpu0", "compute", 1 * s, 2 * s, None,
                 phases=(("kernel", 1 * s, 2 * s),)),
        BusEvent("tpu0", "copy_out", 2 * s, 6 * s, "bus", wait=0.5 * s,
                 phases=(("d2h", 2 * s, 3 * s), ("store", 3 * s, 6 * s))),
        BusEvent("tpu1", "copy_out", 6 * s, 9 * s, "bus", wait=4 * s,
                 phases=(("d2h", 6 * s, 6.5 * s), ("store", 6.5 * s, 9 * s))),
    ])


def _read(name, run):
    return harness.reader(ROOT, name)(run)


@pytest.mark.parametrize("name, want", [
    # per job the chips' phases (or waits) summed; the mean over two jobs,
    # the second at twice the times of the first
    ("chip_d2h_s", 1.5 * 1.5),
    ("chip_store_s", 5.5 * 1.5),
    ("cpu_put_s", 3.0 * 1.5),
    ("bus_wait_s", 5.5 * 1.5),
])
def test_readers_on_a_synthetic_run(name, want):
    run = _run([_synthetic_job(1.0), _synthetic_job(2.0)])
    assert _read(name, run) == pytest.approx(want)


def test_cpu_put_counts_only_the_jobs_that_gave_the_cpu_rows():
    no_cpu = _job([e for e in _synthetic_job(1.0).report.measured.events
                   if e.device != "host-cpu"])
    run = _run([_synthetic_job(1.0), no_cpu])
    assert _read("cpu_put_s", run) == pytest.approx(3.0)
    assert _read("chip_d2h_s", run) == pytest.approx(1.5)


@dataclasses.dataclass(frozen=True)
class _PlainEvent:
    """A stage event as a program without phases records it."""
    device: str
    kind: str
    start: float
    end: float
    link: str | None = None
    chunk: int = 0
    task: str | None = None

    @property
    def duration(self):
        return self.end - self.start


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_phases(name):
    plain = [_PlainEvent(e.device, e.kind, e.start, e.end, e.link)
             for e in _synthetic_job(1.0).report.measured.events]
    no_phases = [dataclasses.replace(e, phases=())
                 for e in _synthetic_job(1.0).report.measured.events]
    assert _read(name, _run([_job(plain)])) is None
    assert _read(name, _run([_job(no_phases)])) is None
    assert _read(name, _run([])) is None


@pytest.mark.parametrize("name", READERS)
def test_each_reader_lists_the_cells_it_finds_phases_in(name):
    # every cell runs chips over a link; only a host CPU partition has a put
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [harness.load_cell(ROOT, w["name"]) for w in bench["workloads"]]
    want = [c.name for c in cells
            if name != "cpu_put_s" or c.config["host_cpu"]]
    metric = {m["name"]: m for m in bench["per_layer"]}[name]
    assert metric["workloads"] == want
    assert metric["source"] == "program_span" and metric["moves"] == "gemm_s"


def test_two_chips_on_one_serialized_link_wait_for_each_other():
    cpu = jax.devices("cpu")[0]
    profiles = [DeviceProfile(f"tpu{i}", "tpu", LinearTimeModel(a=2e-10, b=1e-5),
                              CopyModel(2e9, dtype_size=2), align_m=8)
                for i in range(2)]
    hg = HGemms(profiles, bind={p.name: cpu for p in profiles},
                bus="serialized", interpret=True)
    assert all(asg.m > 0 for asg in hg.plan(256, 384, 128).adapted.assignments)
    rng = np.random.default_rng(5)
    a = rng.integers(-16, 16, (256, 128)).astype(ml_dtypes.bfloat16)
    b = rng.integers(-16, 16, (128, 384)).astype(ml_dtypes.bfloat16)
    hg.execute(a, b)                      # compile first
    jobs = []
    for _ in range(2):
        c, rep = hg.execute(a, b)
        np.testing.assert_array_equal(
            c, a.astype(np.float64) @ b.astype(np.float64))
        jobs.append(harness.Job((256, 384, 128), 0.0, rep, c[:1]))
    run = harness.Run(chips=[cpu], profiles=profiles, setup_s=0.0,
                      predict_s=0.0, plan_s=[], jobs=jobs, window_s=1.0)
    assert _read("bus_wait_s", run) > 0
    assert _read("chip_d2h_s", run) > 0 and _read("chip_store_s", run) > 0
    assert _read("cpu_put_s", run) is None        # no host CPU partition
    # the waits are the link's: one link carries every copy
    for job in jobs:
        assert len({e.link for e in job.report.measured.events
                    if e.kind != "compute"}) == 1
