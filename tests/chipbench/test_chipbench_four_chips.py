"""The four-chip cell's program path and its two readers: four bound chips
(the kernel in interpret mode) plus the host CPU give C as the float64
reference does at every partition's edges, ``fit_spread`` reads the pools
of alike chips and ``chips_used`` the chips that computed rows; each reads
nothing where the program's reports lack what it reads."""
import json
import math
import pathlib
import sys
import types

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness, reference  # noqa: E402
from chipbench.operands import make_operands  # noqa: E402
from repro.core import (NO_COPY, CopyModel, DeviceProfile, HGemms,  # noqa: E402
                        LinearTimeModel)
from repro.core.predict import Pool  # noqa: E402

CONFIG = json.loads((ROOT / "chipbench/configs/v5e4-cpu-host.json").read_text())


def _read(name, run):
    return harness.reader(ROOT, name)(run)


def _run(profiles, reports):
    jobs = [harness.Job((8, 8, 8), 1.0, rep, np.zeros(0)) for rep in reports]
    return harness.Run(chips=[], profiles=profiles, setup_s=0.0,
                       predict_s=0.0, plan_s=[], jobs=jobs, window_s=1.0)


def _profiles(fits=(1.00, 1.02, 0.99, 1.01)):
    """The host CPU and four chips whose fits differ by a few percent; slow
    enough beside their link that every chip is worth its B copy."""
    return [DeviceProfile("host-cpu", "cpu", LinearTimeModel(a=2e-8), NO_COPY)
            ] + [DeviceProfile(f"tpu{i}", "tpu", LinearTimeModel(a=2e-9 * f),
                               CopyModel(1e12 / f, dtype_size=2), align_m=8)
                 for i, f in enumerate(fits)]


def test_four_bound_chips_and_the_cpu_match_the_reference():
    m, n, k = 512, 384, 1024
    cpu = jax.devices("cpu")[0]
    profiles = _profiles()
    hg = HGemms(profiles, bind={p.name: cpu for p in profiles},
                interpret=True)
    assert [p.members for p in hg.pools] == [("tpu0", "tpu1", "tpu2", "tpu3")]
    plan = hg.plan(m, n, k)
    parts = [(a.row0, a.m) for a in plan.adapted.assignments]
    assert all(rows > 0 for _, rows in parts), parts
    seed = 3_000_000_019
    a, b = make_operands(seed, m, n, k, cpu)
    c, rep = hg.execute(a, b)
    rows = reference.sample_rows(parts, seed,
                                 CONFIG["check"]["rows_per_partition"])
    for row0, count in parts:
        assert {row0, row0 + count - 1} <= set(rows.tolist())
    err = reference.max_rel_err(c[rows], reference.reference(a, b, rows))
    assert err <= CONFIG["check"]["max_rel_err"], err
    assert rep.pools == hg.pools
    run = _run(profiles, [rep])
    assert _read("chips_used", run) == 4
    assert _read("fit_spread", run) == pytest.approx(100 * (1.02 / 0.99 - 1))


def test_readers_read_nothing_without_what_they_read():
    profiles = _profiles()
    bare = types.SimpleNamespace()
    assert _read("fit_spread", _run(profiles, [bare])) is None
    assert _read("chips_used", _run(profiles, [bare])) is None
    assert _read("fit_spread", _run(profiles, [])) is None
    assert _read("chips_used", _run(profiles, [])) is None
    # a program that pools nothing (one chip) has no spread to read
    unpooled = types.SimpleNamespace(pools=(), placement={"tpu0": {0}})
    assert _read("fit_spread", _run(profiles, [unpooled])) is None


def test_readers_on_fabricated_reports():
    profiles = _profiles()
    cpu, chip = object(), object()
    reports = [
        types.SimpleNamespace(
            pools=(Pool(("tpu0", "tpu1", "tpu2", "tpu3"), 0.031),),
            placement={"host-cpu": {cpu}, "tpu0": {chip}, "tpu1": {chip}}),
        types.SimpleNamespace(
            pools=(Pool(("tpu0", "tpu1", "tpu2", "tpu3"), 0.031),),
            placement={"host-cpu": {cpu}, "tpu0": {chip}, "tpu1": {chip},
                       "tpu2": {chip}}),
    ]
    run = _run(profiles, reports)
    assert _read("fit_spread", run) == pytest.approx(3.1)
    # the host CPU computed rows too, but it has no host link: not a chip
    assert _read("chips_used", run) == pytest.approx(2.5)
    assert math.isinf(profiles[0].copy.bandwidth_bytes_per_s)
