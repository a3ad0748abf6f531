"""Alike devices: Predict pools the fits of bound devices of one hardware
identity, and Optimize chooses how many of a group of alike devices to use
on a contended topology, so that a plan over four chips is one plan run
after run and offering one more alike chip never makes it worse."""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import (NO_COPY, CopyModel, DeviceProfile, HGemms,
                        LinearTimeModel, paper_mach1, paper_mach2,
                        solve_bisection)
from repro.core.optimize import alike_groups
from repro.core.predict import Pool, pool_profiles

I1 = 30000
N_I1 = float(I1) ** 3


def _host_cpu():
    return DeviceProfile("host-cpu", "cpu", LinearTimeModel(1 / 255e9),
                         NO_COPY)


def _chip(i, speed=37e12, link=6.75e9):
    """A v5e chip as its Predict fits read (37 TMAC/s, 6.75 GB/s bf16)."""
    return DeviceProfile(f"tpu{i}", "tpu", LinearTimeModel(1 / speed),
                         CopyModel(link, dtype_size=2), align_m=8)


def _i1(devices):
    return solve_bisection(devices, N_I1, n=I1, k=I1, bus="serialized")


# -- Optimize: how many alike devices --------------------------------------


def test_offering_one_more_alike_chip_never_worsens_the_plan():
    spans = [_i1([_host_cpu()] + [_chip(i) for i in range(j)]).makespan
             for j in range(1, 5)]
    for fewer, more in zip(spans, spans[1:]):
        assert more <= fewer + 1e-12
    # one chip + CPU 1.512 s; two chips 1.157 s; the greedy over three or
    # four alike chips alone stopped at 1.327 s
    assert spans[0] == pytest.approx(1.5117, abs=2e-3)
    assert spans[3] == pytest.approx(1.159, abs=5e-3)
    assert spans[3] == spans[1]


def test_four_alike_chips_use_two_and_record_every_count():
    r = _i1([_host_cpu()] + [_chip(i) for i in range(4)])
    assert [c > 0 for c in r.ops] == [True, True, True, False, False]
    assert [used for used, _ in r.counts] == [4, 1, 2, 3]
    tried = dict(r.counts)
    assert tried[4] == pytest.approx(1.327, abs=5e-3)
    assert tried[2] == pytest.approx(r.makespan) == min(tried.values())
    assert sum(r.ops) == pytest.approx(N_I1)


def test_the_count_search_keeps_a_plan_no_prefix_beats():
    # chips apart by their models are no group: one solve, nothing recorded
    devices = [_host_cpu(), _chip(0), _chip(1, speed=30e12)]
    assert alike_groups(devices) == []
    assert _i1(devices).counts == ()
    # on an independent topology every chip keeps its rows
    alike = [_host_cpu()] + [_chip(i) for i in range(4)]
    r = solve_bisection(alike, N_I1, n=I1, k=I1, bus="independent")
    assert r.counts == () and all(c > 0 for c in r.ops[1:])


def test_alike_groups_ignore_names_and_keep_index_order():
    devices = [_chip(0), _host_cpu(), _chip(1), _chip(2, speed=1e12),
               _chip(3)]
    assert alike_groups(devices) == [[0, 2, 4]]


# -- Predict: pooling bound devices ------------------------------------------


def _jittered(seed, chips=4, jitter=0.03):
    rng = np.random.default_rng(seed)
    return [_host_cpu()] + [
        _chip(i, speed=37e12 * (1 + jitter * rng.uniform(-1, 1)),
              link=6.75e9 * (1 + jitter * rng.uniform(-1, 1)))
        for i in range(chips)]


def test_pooling_gives_alike_devices_the_median_of_their_fits():
    profiles = [_host_cpu(), _chip(0, 36e12, 6.0e9), _chip(1, 37e12, 7.0e9),
                _chip(2, 40e12, 6.5e9)]
    pooled, pools = pool_profiles(profiles, lambda p: p.kind)
    assert [p.name for p in pooled] == [p.name for p in profiles]
    assert pooled[0] is profiles[0]            # alone in its kind: as given
    assert {p.compute for p in pooled[1:]} == {LinearTimeModel(1 / 37e12)}
    assert {p.copy for p in pooled[1:]} == {CopyModel(6.5e9, dtype_size=2)}
    assert pooled[1:] == [dataclasses.replace(
        pooled[1], name=p.name) for p in profiles[1:]]
    (pool,) = pools
    assert isinstance(pool, Pool) and pool.members == ("tpu0", "tpu1", "tpu2")
    # the widest of the two: bandwidths 7.0 / 6.0, above speeds 40 / 36
    assert pool.spread == pytest.approx(7.0 / 6.0 - 1)


def test_unbound_and_one_chip_plans_are_the_solver_s_own():
    for devices in (paper_mach1(), paper_mach2()):
        hg = HGemms(devices)
        assert hg.pools == () and hg.devices == devices
        p = hg.plan(30000, 30000, 30000)
        r = solve_bisection(devices, N_I1, n=I1, k=I1, bus="serialized")
        assert p.optimize == r
    # unbound alike devices are not pooled: their kinds may be other models
    apart = [_host_cpu(), _chip(0), _chip(1, speed=30e12)]
    assert HGemms(apart).devices == apart
    cpu = jax.devices("cpu")[0]
    one = [_host_cpu(), _chip(0, 36.3e12, 6.61e9)]
    hg = HGemms(one, bind={p.name: cpu for p in one})
    assert hg.pools == () and all(a is b for a, b in zip(hg.devices, one))
    p = hg.plan(30000, 30000, 30000)
    assert p.optimize == solve_bisection(one, N_I1, n=I1, k=I1,
                                         bus="serialized")
    assert p.optimize.counts == ()


def test_pool_and_count_spans_land_in_the_profiler_trace(tmp_path):
    cpu = jax.devices("cpu")[0]
    profiles = _jittered(0)
    with jax.profiler.trace(str(tmp_path)):
        hg = HGemms(profiles, bind={p.name: cpu for p in profiles})
        hg.plan(30000, 30000, 30000)
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("poas."):
                    spans.setdefault(ev.name, []).append(dict(ev.stats))
    (pool,) = spans["poas.predict.pool"]
    assert pool["kind"] == "tpu" and pool["members"] == "tpu0 tpu1 tpu2 tpu3"
    assert float(pool["spread"]) == pytest.approx(hg.pools[0].spread)
    counts = spans["poas.plan.optimize.count"]
    assert sorted(int(s["used"]) for s in counts) == [1, 2, 3]
    assert all(float(s["makespan"]) > 0 for s in counts)


FOUR_CHIPS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import jax
sys.path.insert(0, "tests")
from test_alike_devices import _jittered
from repro.core import HGemms

cpus = jax.devices("cpu")
assert len(cpus) == 4
plans = []
for seed in range(20):
    profiles = _jittered(seed)
    bind = {"host-cpu": cpus[0], **{f"tpu{i}": cpus[i] for i in range(4)}}
    hg = HGemms(profiles, bind=bind)
    p = hg.plan(30000, 30000, 30000)
    plans.append({"rows": {a.device: a.m for a in p.adapted.assignments},
                  "pools": [list(q.members) for q in hg.pools],
                  "spread": max(q.spread for q in hg.pools)})
print("PLANS", json.dumps(plans))
"""


def test_jittered_chips_bound_to_four_devices_plan_alike_on_every_seed():
    root = pathlib.Path(__file__).resolve().parents[1]
    r = subprocess.run([sys.executable, "-c", FOUR_CHIPS],
                       capture_output=True, text=True, cwd=root,
                       env={**os.environ, "PYTHONPATH": "src"})
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("PLANS ")]
    assert lines, r.stderr[-2000:]
    plans = json.loads(lines[0][len("PLANS "):])
    assert len(plans) == 20
    for p in plans:
        assert p["pools"] == [["tpu0", "tpu1", "tpu2", "tpu3"]]
        assert 0 < p["spread"] < 0.07
    # the same devices every seed: the CPU and the first two chips
    used = {tuple(d for d, m in p["rows"].items() if m > 0) for p in plans}
    assert used == {("host-cpu", "tpu0", "tpu1")}
    # the rows move only with the pooled medians (±3% fits move them
    # about ±1.5%), never by a chip's share changing hands
    for dev in ("host-cpu", "tpu0", "tpu1"):
        rows = [p["rows"][dev] for p in plans]
        assert max(rows) - min(rows) <= 0.02 * I1, (dev, rows)
