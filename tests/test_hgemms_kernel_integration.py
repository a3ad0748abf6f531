"""hgemms partitions executed through the Pallas matmul kernel (interpret
mode) on bound JAX devices — the full paper pipeline down to the TPU compute
unit, rehearsed on the CPU device."""
import importlib.util
import pathlib

import jax
import ml_dtypes
import numpy as np
import pytest

from repro.core import (NO_COPY, CopyModel, DeviceProfile, HGemms,
                        LinearTimeModel, paper_mach1)

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def _host_and_chip():
    """A host CPU (computes in place) and a chip fed over a bf16 link."""
    return [DeviceProfile("host", "cpu", LinearTimeModel(a=2e-9, b=1e-5),
                          NO_COPY),
            DeviceProfile("chip", "tpu", LinearTimeModel(a=2e-10, b=1e-5),
                          CopyModel(2e9, dtype_size=2), align_m=8)]


def _bound_gemm(m=96, n=256, k=128, seed=0):
    cpu = jax.devices("cpu")[0]
    profiles = _host_and_chip()
    hg = HGemms(profiles, bind={p.name: cpu for p in profiles},
                interpret=True)
    rng = np.random.default_rng(seed)
    # integers in [-16, 16): exact in bf16, every partial sum exact in f32
    a = rng.integers(-16, 16, (m, k)).astype(ml_dtypes.bfloat16)
    b = rng.integers(-16, 16, (k, n)).astype(ml_dtypes.bfloat16)
    return hg, cpu, a, b


def test_poas_partitions_via_pallas_kernel():
    hg, cpu, a, b = _bound_gemm()
    plan = hg.plan(a.shape[0], b.shape[1], a.shape[1])
    assert all(asg.m > 0 for asg in plan.adapted.assignments)
    c, rep = hg.execute(a, b)
    assert c.dtype == np.float32
    np.testing.assert_array_equal(c, a.astype(np.float64) @ b.astype(np.float64))
    assert rep.placement == {"host": {cpu}, "chip": {cpu}}
    chip = {e.kind: e for e in rep.measured.device_events("chip")}
    assert set(chip) == {"copy_in", "compute", "copy_out"}
    for kind in ("copy_in", "copy_out"):
        assert chip[kind].end > chip[kind].start
    assert chip["compute"].start >= chip["copy_in"].end
    assert chip["copy_out"].start >= chip["compute"].end
    assert [e.kind for e in rep.measured.device_events("host")] == ["compute"]


def test_bound_warm_execute_compiles_nothing():
    hg, _, a, b = _bound_gemm(seed=1)
    hg.execute(a, b)                  # cold: traces and compiles
    compiles = []

    def listen(event, duration, **kw):
        if event in COMPILE_EVENTS:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        c, _ = hg.execute(a, b)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    np.testing.assert_array_equal(c, a.astype(np.float64) @ b.astype(np.float64))


def test_bind_must_name_every_profile():
    profiles = _host_and_chip()
    with pytest.raises(ValueError, match="bind must name"):
        HGemms(profiles, bind={"host": jax.devices("cpu")[0]})


def _load_chip_smoke():
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("chips", [1, 4], ids=["1chip", "4chip"])
def test_chip_smoke_phases_rehearsed_on_cpu(chips, capsys):
    """chip_smoke.py's Predict and Run phases at a tiny size, with the CPU
    device bound in every chip's place and the kernel interpreted."""
    cs = _load_chip_smoke()
    cpu = jax.devices("cpu")[0]
    fitted = cs.predict([cpu] * chips, cpu, cpu_sizes=(64, 128),
                        chip_sizes=(64, 128), interpret=True)
    assert [p.name for p in fitted] == ["host-cpu"] + [
        f"tpu{i}" for i in range(chips)]
    assert all(p.compute.a > 0 for p in fitted)
    assert all(p.copy.dtype_size == 2 for p in fitted[1:])
    # set rates by hand: fitted on the CPU, the interpreted kernel would
    # get no rows and its path would go unexercised
    profiles = [DeviceProfile("host-cpu", "cpu",
                              LinearTimeModel(a=2e-9, b=1e-5), NO_COPY)] + [
        DeviceProfile(f"tpu{i}", "tpu", LinearTimeModel(a=2e-10, b=1e-5),
                      CopyModel(2e9, dtype_size=2), align_m=8)
        for i in range(chips)]
    a, b = cs.operands(0, 128, 256, 128)
    counter = cs.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        walls = cs.run_case("rehearsal", profiles, [cpu] * (chips + 1), a, b,
                            counter, interpret=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
    # run_case itself fails on a wrong row, a wrong device or a warm compile
    assert set(walls) == {"cold", "warm"}
    assert "[check] rehearsal warm: tpu0" in capsys.readouterr().out


def test_subproducts_cover_each_partition():
    """Adapt-phase sub-products tile each device slice exactly."""
    hg = HGemms(paper_mach1())
    plan = hg.plan(4096, 1024, 2048)
    for asg in plan.adapted.assignments:
        if asg.m == 0 or not asg.sub_products:
            continue
        area = sum(t.m * t.k for t in asg.sub_products)
        assert area == asg.m * plan.adapted.k
        # no tile exceeds the slice bounds
        for t in asg.sub_products:
            assert 0 <= t.row0 and t.row0 + t.m <= asg.m
            assert 0 <= t.k0 and t.k0 + t.k <= plan.adapted.k
