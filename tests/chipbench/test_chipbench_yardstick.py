"""The chip benchmark's yardstick: roofline arithmetic, the peaks table, the
trace reduction and the comparison that decides ``correct``."""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import reference, roofline, trace  # noqa: E402

V5E = roofline.peaks_for("TPU v5 lite")


def test_least_time_compute_bound_at_i1():
    t, bound = roofline.least_time(30000, 30000, 30000, V5E)
    assert bound == "compute"
    assert t == pytest.approx(2 * 30000.0 ** 3 / 197e12)
    # the bytes bound, (A + B) in bf16 and C in f32, is far below it
    assert roofline.gemm_bytes(30000, 30000, 30000) == 2 * 9e8 * 2 + 9e8 * 4
    assert roofline.gemm_bytes(30000, 30000, 30000) / 819e9 < t / 30


def test_least_time_memory_bound_for_a_thin_partition():
    m, n, k = 8, 30000, 30000
    t, bound = roofline.least_time(m, n, k, V5E)
    assert bound == "memory"
    want = ((m * k + k * n) * 2 + m * n * 4) / 819e9
    assert t == pytest.approx(want)
    assert t > roofline.gemm_flop(m, n, k) / 197e12


def test_peaks_lookup_fails_on_an_unknown_device_kind():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        roofline.peaks_for("TPU v9 imaginary")


def test_every_peak_names_its_source():
    table = json.loads(roofline.PEAKS.read_text())
    for kind, row in table.items():
        assert row["source"] and row["bf16_flop_per_s"] > 0, kind
        assert row["hbm_bytes_per_s"] > 0, kind


@pytest.mark.parametrize("intervals, want", [
    ([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)], 3.0),     # overlap merges
    ([(0.0, 4.0), (1.0, 2.0)], 4.0),                 # nested adds nothing
    ([(2.0, 3.0), (0.0, 1.0)], 2.0),                 # any order
    ([], 0.0),
])
def test_busy_union_of_overlapping_ops(intervals, want):
    assert trace.union_seconds(intervals) == pytest.approx(want)


def test_idle_gaps_are_the_window_less_the_union():
    ivs = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0)]
    gaps = trace.idle_gaps(ivs, 0.0, 7.0)
    assert gaps == [(0.0, 1.0), (3.0, 5.0), (6.0, 7.0)]
    busy = trace.union_seconds(ivs)
    assert busy + sum(e - s for s, e in gaps) == pytest.approx(7.0)


def test_program_name_drops_the_run_suffix():
    assert trace.program_name("jit_matmul(1234)") == "jit_matmul"
    assert trace.program_name("jit_matmul") == "jit_matmul"


def test_sample_rows_cover_every_partition_and_its_boundaries():
    parts = [(0, 432), (432, 29568)]
    rows = reference.sample_rows(parts, 2**31 + 11, 16)
    for row0, n in parts:
        assert {row0, row0 + n - 1} <= set(rows)
        assert ((rows >= row0) & (rows < row0 + n)).sum() >= 8
    assert np.array_equal(rows, reference.sample_rows(parts, 2**31 + 11, 16))
    assert not np.array_equal(rows, reference.sample_rows(parts, 5, 16))


def test_bf16_widening_is_exact():
    import ml_dtypes

    x = np.random.default_rng(0).standard_normal(1000).astype(
        ml_dtypes.bfloat16)
    assert np.array_equal(reference.bf16_to_f64(x), x.astype(np.float64))


def test_reference_equals_a_direct_float64_product():
    import ml_dtypes

    rng = np.random.default_rng(1)
    a = rng.uniform(-1, 1, (40, 300)).astype(ml_dtypes.bfloat16)
    b = rng.uniform(-1, 1, (300, 70)).astype(ml_dtypes.bfloat16)
    rows = np.array([0, 7, 39])
    want = a.astype(np.float64)[rows] @ b.astype(np.float64)
    got = reference.reference(a, b, rows, block=32)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)


def test_max_rel_err_reads_nan_as_infinite():
    ref = np.ones((2, 3))
    got = ref.copy()
    assert reference.max_rel_err(got, ref) == 0.0
    got[1, 2] = np.nan
    assert reference.max_rel_err(got, ref) == float("inf")


# -- the reduction of a trace ------------------------------------------------------

# An XSpace laid out as the profiler lays out a TPU trace: a device plane with
# "XLA Modules" and "XLA Ops" lines, and the host plane holding the
# benchmark's annotations.  Times in ns: the window is [0, 10000]; a job
# [0, 6000] and a sample [6000, 9000] inside it; on TPU 0 two overlapping ops
# [1000, 2000] and [1500, 3500] in one run of jit_matmul, and one op
# [7000, 8000] of another program; TPU 1 ran nothing.
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2500000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_matmul(7)" } }
  event_metadata { key: 2 value { id: 2 name: "fusion" } }
  event_metadata { key: 3 value { id: 3 name: "tpu_custom_call" } }
  event_metadata { key: 4 value { id: 4 name: "jit_other(9)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 6000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench.job" } }
  event_metadata { key: 3 value { id: 3 name: "chipbench.sample" } }
}
"""


@pytest.fixture(scope="module")
def reduction():
    import jax

    profile = jax.profiler.ProfileData.from_text_proto(XSPACE)
    return trace.reduce(profile, [0, 1], "chipbench.window")


def test_reduction_busy_union_and_window(reduction):
    assert reduction.window_s == pytest.approx(10e-6)
    # ops [1000, 2000] and [1500, 3500] overlap: 2500 ns, plus 1000 ns later
    assert reduction.busy[0] == pytest.approx(3.5e-6)
    # a chip with no plane in the trace counts as idle
    assert reduction.busy[1] == 0.0
    assert reduction.busy_s == pytest.approx(3.5e-6 / 2)


def test_reduction_program_time_and_ops(reduction):
    assert reduction.programs[0] == {"jit_matmul": [pytest.approx(2.5e-6)],
                                     "jit_other": [pytest.approx(1e-6)]}
    assert reduction.ops["fusion"] == pytest.approx(2e-6)
    assert reduction.ops["tpu_custom_call"] == pytest.approx(2e-6)


def test_reduction_labels_each_gap_by_the_open_annotation(reduction):
    assert sorted(reduction.gaps) == [
        ("chipbench.job tpu:0", pytest.approx(1e-6)),      # [0, 1000]
        ("chipbench.job tpu:0", pytest.approx(3.5e-6)),    # [3500, 7000]
        ("chipbench.job tpu:1", pytest.approx(10e-6)),     # all of it
        ("chipbench.window tpu:0", pytest.approx(2e-6)),   # [8000, 10000]
    ]
    top = reduction.breakdown()
    assert top["idle_gaps"][0] == ["chipbench.job tpu:1",
                                   pytest.approx(10e-6)]
    assert [name for name, _ in top["device_ops"]] == ["fusion",
                                                       "tpu_custom_call"]
