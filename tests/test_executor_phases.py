"""The executor's phases and ticket waits: recorded in each stage's
``BusEvent`` on the stream clock, and written to the profiler's trace as
``poas.*`` host spans on the device's clock, from one recording path."""
import functools
import pathlib
import threading
import time

import jax
import ml_dtypes
import numpy as np
import pytest

from repro.core import (NO_COPY, CopyModel, DeviceProfile, DeviceTask, HGemms,
                        LinearTimeModel, StreamCore)
from repro.core.executor import phase
from repro.core.hgemms import host_matmul, mxu_matmul, row_major_matmul


def _bound(pipeline_chunks=None, m=256, n=384, k=128):
    """A host CPU computing in place and a chip fed over a bf16 link, both
    bound to the CPU device (the chip's kernel in interpret mode)."""
    profiles = [
        DeviceProfile("host", "cpu", LinearTimeModel(a=2e-9, b=1e-5), NO_COPY),
        DeviceProfile("chip", "tpu", LinearTimeModel(a=2e-10, b=1e-5),
                      CopyModel(2e9, dtype_size=2), align_m=8)]
    cpu = jax.devices("cpu")[0]
    hg = HGemms(profiles, bind={p.name: cpu for p in profiles},
                pipeline_chunks=pipeline_chunks, interpret=True)
    rng = np.random.default_rng(3)
    a = rng.integers(-16, 16, (m, k)).astype(ml_dtypes.bfloat16)
    b = rng.integers(-16, 16, (k, n)).astype(ml_dtypes.bfloat16)
    return hg, a, b


def _names(ev):
    return [name for name, _, _ in ev.phases]


def _inside(ev):
    """Every phase lies inside its stage's interval, in order, and together
    they take no more than the stage."""
    t = ev.start
    for _, start, end in ev.phases:
        assert t <= start <= end <= ev.end
        t = end
    assert sum(end - start for _, start, end in ev.phases) <= ev.duration


def test_bound_execute_records_phases_inside_each_stage():
    hg, a, b = _bound()
    assert all(asg.m > 0 for asg in hg.plan(256, 384, 128).adapted.assignments)
    c, rep = hg.execute(a, b)
    np.testing.assert_array_equal(c, a.astype(np.float64) @ b.astype(np.float64))
    chip = {e.kind: e for e in rep.measured.device_events("chip")}
    host = {e.kind: e for e in rep.measured.device_events("host")}
    assert _names(chip["copy_out"]) == ["d2h", "store"]
    assert _names(host["compute"]) == ["put", "kernel", "store"]
    assert _names(chip["compute"]) == ["kernel"]
    assert _names(chip["copy_in"]) == []
    for ev in rep.measured.events:
        _inside(ev)
        assert ev.wait >= 0.0
    # the host computes in place: it takes no link and waits for no ticket
    assert host["compute"].link is None and host["compute"].wait == 0.0


def test_chunked_plan_records_phases_per_chunk():
    hg, a, b = _bound(pipeline_chunks=2)
    plan = hg.plan(256, 384, 128)
    chip_asg = [asg for asg in plan.adapted.assignments
                if asg.device == "chip"][0]
    assert len(chip_asg.chunk_rows) == 2
    c, rep = hg.execute(a, b)
    np.testing.assert_array_equal(c, a.astype(np.float64) @ b.astype(np.float64))
    chip = rep.measured.device_events("chip")
    for kind, names in (("copy_out", ["d2h", "store"]),
                        ("compute", ["kernel"])):
        evs = sorted((e for e in chip if e.kind == kind),
                     key=lambda e: e.chunk)
        assert [e.chunk for e in evs] == [0, 1]
        for e in evs:
            assert _names(e) == names
            _inside(e)
    # the chunks share one ticket: only chunk 0 can have waited for it
    for e in chip:
        if e.chunk > 0:
            assert e.wait == 0.0


def test_second_device_on_a_serialized_link_waits_for_the_holder():
    core = StreamCore()
    hold = 0.25

    def slow_copy():
        time.sleep(hold)

    tasks = [DeviceTask("a", copy_in=slow_copy, compute=lambda: None,
                        copy_out=None),
             DeviceTask("b", copy_in=lambda: None, compute=lambda: None,
                        copy_out=None)]
    try:
        tl = core.run(tasks, {"bus": [("a", "copy_in"), ("b", "copy_in")]})
    finally:
        core.shutdown()
    ins = {e.device: e for e in tl.events if e.kind == "copy_in"}
    assert ins["a"].wait < 0.05
    assert ins["a"].duration >= hold
    # b asked for the link at once and got it when a released it
    assert ins["b"].wait == pytest.approx(ins["a"].duration, abs=0.05)
    assert ins["b"].start >= ins["a"].end


def _host_spans(log_dir):
    path = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))[-1]
    profile = jax.profiler.ProfileData.from_file(str(path))
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "caller" or ev.name.startswith("poas."):
                    spans.append((ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9,
                                  dict(ev.stats)))
    return spans


def test_spans_land_in_the_profiler_trace_as_the_report_times_them(tmp_path):
    hg, a, b = _bound()
    hg.execute(a, b)                      # compile outside the trace
    hg.plan_cache.invalidate()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("caller"):
            _, rep = hg.execute(a, b)
            with phase("outside"):        # no stage: a span, nothing recorded
                pass
    spans = _host_spans(tmp_path)
    (caller,) = [s for s in spans if s[0] == "caller"]
    poas = [s for s in spans if s[0].startswith("poas.")]
    names = {s[0] for s in poas}
    assert {"poas.execute", "poas.plan", "poas.plan.optimize",
            "poas.plan.adapt", "poas.plan.schedule", "poas.bus_wait",
            "poas.copy_in", "poas.compute", "poas.copy_out",
            "poas.copy_out.d2h", "poas.copy_out.store", "poas.compute.put",
            "poas.compute.kernel", "poas.compute.store",
            "poas.outside"} <= names
    for _, start, end, _ in poas:
        assert caller[1] <= start <= end <= caller[2]
    # each stage and phase: its span's arguments name it, and the span lasts
    # what the report says, within 1 ms
    for ev in rep.measured.events:
        want = [(f"poas.{ev.kind}", ev.duration)] + [
            (f"poas.{ev.kind}.{name}", end - start)
            for name, start, end in ev.phases]
        for name, seconds in want:
            (span,) = [s for s in poas if s[0] == name
                       and s[3].get("device") == ev.device]
            assert span[3]["chunk"] == ev.chunk
            assert span[3]["job"] == "job0"
            assert span[2] - span[1] == pytest.approx(seconds, abs=1e-3)
    (d2h,) = [s for s in poas if s[0] == "poas.copy_out.d2h"]
    rows = [asg.m for asg in rep.plan.adapted.assignments
            if asg.device == "chip"][0]
    assert d2h[3]["bytes"] == rows * b.shape[1] * 4


@pytest.mark.parametrize("pipeline_chunks", [None, 2],
                         ids=["one-chunk", "two-chunks"])
def test_chip_rows_are_stored_row_major_as_the_unpinned_kernel_computes_them(
        tmp_path, pipeline_chunks):
    """A chip share that is not square: C is, bit for bit, what the
    unpinned chip kernel and the host's kernel give its rows, and every
    store of the chip's rows into C copies row-major rows."""
    m, n, k = 200, 384, 256
    hg, _, _ = _bound(pipeline_chunks=pipeline_chunks, m=m, n=n, k=k)
    rng = np.random.default_rng(5)
    a = rng.uniform(-1, 1, (m, k)).astype(ml_dtypes.bfloat16)
    b = rng.uniform(-1, 1, (k, n)).astype(ml_dtypes.bfloat16)
    plan = hg.plan(m, n, k)
    kernels = {"host": host_matmul,
               "chip": functools.partial(mxu_matmul, interpret=True)}
    want = np.zeros((m, n), np.float32)
    for asg in plan.adapted.assignments:
        assert 0 < asg.m != n
        for r0, rows in zip(asg.chunk_offsets(), asg.chunk_rows):
            want[r0:r0 + rows] = np.asarray(
                kernels[asg.device](a[r0:r0 + rows], b))
    cpu = jax.devices("cpu")[0]
    (chip,) = [p for p in hg.devices if p.name == "chip"]
    assert hg._target(chip)[1] is row_major_matmul(cpu, True)
    hg.execute(a, b)                      # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        c, rep = hg.execute(a, b)
    np.testing.assert_array_equal(c, want)
    stores = [s for s in _host_spans(tmp_path)
              if s[0] == "poas.copy_out.store"]
    outs = [e for e in rep.measured.device_events("chip")
            if e.kind == "copy_out"]
    assert len(stores) == len(outs) == (pipeline_chunks or 1)
    assert [s[3]["row_major"] for s in stores] == [True] * len(stores)


def test_a_phase_lands_in_the_stage_of_its_own_thread():
    """A phase the stage's callable opens lands in that stage's event; one
    opened on a thread the callable starts, outside any stage, lands
    nowhere."""
    helpers = []

    def aside():
        with phase("aside"):
            pass

    def compute():
        with phase("mine"):
            t = threading.Thread(target=aside)
            helpers.append(t)
            t.start()
            t.join(timeout=10)

    core = StreamCore()
    try:
        tl = core.run([DeviceTask("d", copy_in=None, compute=compute,
                                  copy_out=None)], {})
    finally:
        core.shutdown()
    assert not helpers[0].is_alive()
    (ev,) = tl.events
    assert [name for name, _, _ in ev.phases] == ["mine"]
