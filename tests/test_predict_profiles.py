"""Predict-phase satellites: profile persistence round-trip and the
``fit_linear`` degenerate-branch slope clamp."""
import math

import pytest

from repro.core import (CopyModel, DeviceProfile, LinearTimeModel, NO_COPY,
                        RooflineTimeModel, fit_linear, load_profiles,
                        save_profiles, tpu_group)
from repro.core.optimize import solve_analytic, solve_bisection


# ------------------------------------------------ save/load round-trip ------

def _testbed():
    return [
        # linear model + NO_COPY (host CPU computing in place)
        DeviceProfile("cpu", "cpu", LinearTimeModel(a=7.1e-12, b=1e-4),
                      NO_COPY, align_m=1, cache_bytes=15e6),
        # linear model + finite-bandwidth copy with latency + pipelining
        DeviceProfile("gpu", "gpu", LinearTimeModel(a=1.6e-13, b=2e-4),
                      CopyModel(15.75e9, dtype_size=2, latency_s=3e-5),
                      align_m=8, align_k=8, cache_bytes=6e6,
                      pipeline_chunks=4),
        # roofline model (TPU group)
        tpu_group("tpu", 8, derate=0.9),
    ]


def test_profiles_round_trip(tmp_path):
    path = str(tmp_path / "profiles.json")
    devices = _testbed()
    save_profiles(path, devices)
    loaded = load_profiles(path)
    assert len(loaded) == len(devices)
    for orig, back in zip(devices, loaded):
        assert back == orig   # frozen dataclasses compare by value


def test_profiles_round_trip_preserves_model_types_and_times(tmp_path):
    path = str(tmp_path / "profiles.json")
    save_profiles(path, _testbed())
    cpu, gpu, tpu = load_profiles(path)
    assert isinstance(cpu.compute, LinearTimeModel)
    assert isinstance(gpu.compute, LinearTimeModel)
    assert isinstance(tpu.compute, RooflineTimeModel)
    # NO_COPY survives as the infinite-bandwidth sentinel
    assert math.isinf(cpu.copy.bandwidth_bytes_per_s)
    assert cpu.copy(1e9, 1000, 1000) == 0.0
    # times (the scheduling contract) are identical
    for d0, d1 in zip(_testbed(), (cpu, gpu, tpu)):
        for c in (1e6, 1e9, 5e10):
            assert d1.compute(c) == pytest.approx(d0.compute(c), rel=0.0)
            assert d1.copy(c, 2048, 2048) == pytest.approx(
                d0.copy(c, 2048, 2048), rel=0.0)
        assert d1.pipeline_chunks == d0.pipeline_chunks


def test_loaded_profiles_plan_identically(tmp_path):
    """A plan solved on loaded profiles equals one solved on the originals
    (the round-trip preserves everything the solver reads)."""
    path = str(tmp_path / "profiles.json")
    devices = _testbed()
    save_profiles(path, devices)
    loaded = load_profiles(path)
    r0 = solve_bisection(devices, 1e12, n=4096, k=4096, bus="serialized")
    r1 = solve_bisection(loaded, 1e12, n=4096, k=4096, bus="serialized")
    assert r1.ops == pytest.approx(r0.ops, rel=1e-12)
    assert r1.makespan == pytest.approx(r0.makespan, rel=1e-12)


# -------------------------------------------- fit_linear degenerate ---------

def test_fit_linear_single_size_clamps_slope():
    """Regression (satellite): the single-size branch returned a=0 when
    mx == 0, a zero-slope 'free compute' model every solver must
    special-case; it must clamp to the same 1e-18 floor as the main path."""
    m = fit_linear([0.0], [0.0])
    assert m.a >= 1e-18
    m = fit_linear([0.0, 0.0], [0.0, 0.0])
    assert m.a >= 1e-18


def test_fit_linear_single_size_keeps_throughput():
    # a genuine single-size sample still yields the throughput-only model
    m = fit_linear([2e9, 2e9], [4e-3, 4e-3])
    assert m.a == pytest.approx(2e-12)
    assert m.b == 0.0


def test_fit_linear_degenerate_model_safe_for_solvers():
    """The clamped degenerate model goes straight through both solvers
    without special-casing."""
    devs = [DeviceProfile("deg", "cpu", fit_linear([0.0], [0.0]), NO_COPY),
            DeviceProfile("lin", "gpu", LinearTimeModel(a=1e-12, b=1e-4),
                          NO_COPY)]
    r = solve_analytic(devs, 1e9, n=100, k=100)
    assert sum(r.ops) == pytest.approx(1e9, rel=1e-9)
    r2 = solve_bisection(devs, 1e9, n=100, k=100, bus="independent")
    assert sum(r2.ops) == pytest.approx(1e9, rel=1e-6)
    assert math.isfinite(r2.makespan)


# ------------------------------------------------ real-device profiling -----

def test_device_runner_fits_a_real_device():
    """The profiling pass on a real jax.Device: warmed, block_until_ready
    timings of the partition kernel that regress to a usable model."""
    import jax
    import numpy as np

    from repro.core import Profiler, device_runner
    from repro.core.hgemms import host_matmul

    cpu = jax.devices("cpu")[0]
    prof = Profiler(device_runner(cpu, host_matmul, np.float32), repeats=2)
    records = prof.run([64, 128, 256])
    assert all(r.seconds > 0 for r in records)
    assert prof.fit().a > 0


def test_measure_bandwidth_times_a_real_transfer():
    import jax

    from repro.core import measure_bandwidth

    bw = measure_bandwidth(jax.devices("cpu")[0], nbytes=1 << 20, repeats=3)
    assert math.isfinite(bw) and bw > 0
