"""The chip benchmark as data: every name in BENCHMARK.json resolves to a file
of its own, a new cell is new files only, and a CPU rehearsal of the run loop
(the CPU device bound in the chip's place, the kernel in interpret mode)
decides ``correct`` as a chip run does, faults included."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import control, harness, reference  # noqa: E402
from repro.core.hgemms import host_matmul  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
CELL = "tiny.tiny-cpu"


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert len(names) == len(set(names))
    for name in names:
        assert NAME.fullmatch(name), name
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], set()).add(m["name"])
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_name_resolves_to_a_file_of_its_own(cell):
    c = harness.load_cell(ROOT, cell)
    w = {w["name"]: w for w in BENCH["workloads"]}[cell]
    entry = {c["name"]: c for c in BENCH["configs"]}[w["config"]]
    assert entry["file"].startswith("chipbench/configs/")
    assert c.config["name"] == w["config"]
    assert c.config["reduced"] == entry["reduced"]
    assert c.config["source"] == entry["source"]
    assert c.traffic["name"] == w["traffic"]
    assert c.config["chips"] <= w["chips"]
    assert c.config["check"]["max_rel_err"] > 0
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.reader(ROOT, m["name"])), m["name"]
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    assert "setup_s" in {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2 and c.per_layer


def test_config_files_are_distinct_and_used():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


# -- a new cell made of new files only ------------------------------------------


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout's benchmark with one more cell, made of new files only: a
    configuration, a traffic mix and the cell's entry."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((ROOT / "chipbench/configs/v5e1-cpu.json").read_text())
    cfg["name"] = "tiny-cpu"
    cfg["profile"].update(cpu_sizes=[64, 128], chip_sizes=[128, 256],
                          repeats=1, bandwidth_bytes=1 << 16)
    (tmp_path / "chipbench/configs/tiny-cpu.json").write_text(json.dumps(cfg))
    (tmp_path / "chipbench/traffic/tiny.json").write_text(json.dumps(
        {"name": "tiny", "source": "a CPU rehearsal",
         "jobs": [{"m": 520, "n": 384, "k": 256}]}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny-cpu", "source": "a CPU rehearsal",
                             "file": "chipbench/configs/tiny-cpu.json",
                             "reduced": [], "why": "CPU rehearsal"})
    bench["workloads"].append({"name": CELL, "config": "tiny-cpu",
                               "traffic": "tiny", "chips": 1,
                               "why": "CPU rehearsal"})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m and "i1.v5e1-cpu" in m["workloads"]:
            m["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_a_new_cell_of_new_files_loads_without_an_edit(tiny_root):
    before = {p.relative_to(ROOT / "chipbench"): p.read_bytes()
              for p in (ROOT / "chipbench").rglob("*") if p.is_file()
              and "__pycache__" not in p.parts}
    cell = harness.load_cell(tiny_root, CELL)
    assert cell.config["name"] == "tiny-cpu"
    assert cell.traffic["jobs"] == [{"m": 520, "n": 384, "k": 256}]
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in BENCH["per_layer"]
        if "i1.v5e1-cpu" in m.get("workloads", ["i1.v5e1-cpu"])}
    for rel, data in before.items():
        assert (tiny_root / "chipbench" / rel).read_bytes() == data, rel


def test_job_order_runs_every_size_each_round():
    traffic = {"jobs": [{"m": i, "n": 1, "k": 1} for i in range(1, 5)]}
    order = harness.job_order(traffic, 2**31 + 3)
    rounds = [sorted(next(order)[0] for _ in range(4)) for _ in range(3)]
    assert rounds == [[1, 2, 3, 4]] * 3


# -- CPU rehearsal of the run loop -----------------------------------------------


def rehearse(root, trace=False, seconds=0.2, seed=2**31 + 5):
    import jax

    cpu = jax.devices("cpu")[0]
    cell = harness.load_cell(root, CELL)
    return harness.run(cell, seed, seconds, trace, chips=[cpu], cpu=cpu,
                       t_start=time.perf_counter(), interpret=True)


def test_cpu_rehearsal_of_a_run(tiny_root, capsys):
    res = rehearse(tiny_root)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"gemm_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    chk = res["checks"]["max_rel_err"]
    assert chk["value"] <= chk["limit"]
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-2].startswith("[check] max_rel_err ")
    assert err[-1].startswith("[check] failed_jobs ")


def test_cpu_rehearsal_of_a_traced_run(tiny_root):
    res = rehearse(tiny_root, trace=True)
    assert res["correct"] is True
    got = set(res["metrics"])
    # no chip in a CPU trace: the device readers find nothing and are left out
    assert "idle_share" not in got and "mxu_roofline" not in got
    assert {"predict_s", "plan_ms", "plan_error"} <= got
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _zeros(a, b, **kw):
    import jax.numpy as jnp

    return jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)


def _half_rows(a, b, **kw):
    out = host_matmul(a, b)
    return out.at[out.shape[0] // 2:].set(0.0)


def _altered(a, b, **kw):
    return host_matmul(a, b) * (1.0 + 2.0 ** -9)


@pytest.mark.parametrize("fault, target", [
    (_zeros, "mxu_matmul"),        # a partition returned unchanged: C stays 0
    (_zeros, "host_matmul"),
    (_half_rows, "mxu_matmul"),    # half the partition's rows left out
    (_altered, "host_matmul"),     # every answer altered where produced
    (_altered, "mxu_matmul"),
])
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault,
                                            target):
    import repro.core.hgemms as hgemms

    import dataclasses

    from repro.core import CopyModel, LinearTimeModel

    # fixed device models that give both partitions rows
    real_predict = harness.predict

    def even_predict(*args, **kw):
        return [dataclasses.replace(
            p, compute=LinearTimeModel(a=2e-11 if p.kind == "cpu" else 1e-11),
            copy=p.copy if p.kind == "cpu" else CopyModel(1e12, dtype_size=2))
            for p in real_predict(*args, **kw)]

    monkeypatch.setattr(harness, "predict", even_predict)
    monkeypatch.setattr(hgemms, target, fault)
    res = rehearse(tiny_root)
    assert res["correct"] is False
    assert res["checks"]["max_rel_err"]["value"] > \
        res["checks"]["max_rel_err"]["limit"]


def test_the_control_fails_the_limit_the_program_passes():
    """The control (operands rounded to fp8, the precision below the stated
    bf16) and a C returned in bf16 both read above the limit at a size a test
    run holds; the program's own path reads below it."""
    import jax

    from chipbench.operands import make_operands
    from repro.core.hgemms import mxu_matmul

    cpu = jax.devices("cpu")[0]
    limit = json.loads((ROOT / "chipbench/configs/v5e1-cpu.json").read_text()
                       )["check"]["max_rel_err"]
    a, b = make_operands(2**31 + 9, 256, 256, 2048, cpu)
    rows = reference.sample_rows([(0, 64), (64, 192)], 3, 16)
    for kernel in (host_matmul, lambda x, y: mxu_matmul(x, y, interpret=True)):
        c = np.asarray(kernel(a, b))
        r = control.readings(a, b, c[rows], rows, cpu)
        assert r["program"] < limit / 10
        assert r["fp8"] > 10 * limit
        assert r["bf16_out"] > 3 * limit


# -- the command ------------------------------------------------------------------


def test_the_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = BENCH["command"] + ["--workload", "i1.v5e1-cpu", "--seed",
                              str(2**31 + 1), "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()
