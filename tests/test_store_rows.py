"""The store of a partition's rows into C: split into contiguous row blocks
copied at once by one pool of host threads when it is large enough, one copy
on the calling thread otherwise, and the same C either way."""
import threading

import jax
import numpy as np
import pytest

from repro.core import hgemms
from test_executor_phases import _bound, _host_spans


@pytest.fixture
def cpus(monkeypatch):
    """Pin the usable CPUs the store sees; returns the setter."""
    def pin(n):
        monkeypatch.setattr(hgemms.os, "sched_getaffinity",
                            lambda pid: set(range(n)))
    return pin


@pytest.fixture
def fresh_pool(monkeypatch):
    """A store pool made inside the test and shut down after it."""
    monkeypatch.setattr(hgemms, "_store_pool", None)
    yield
    if hgemms._store_pool is not None:
        hgemms._store_pool.shutdown()


def _chip_store_floor(hg, m, n, k):
    """A block floor that the host partition's store stays under and every
    chip store exceeds."""
    plan = hg.plan(m, n, k).adapted
    row_bytes = n * 4
    (host,) = [a for a in plan.assignments if a.device == "host"]
    (chip,) = [a for a in plan.assignments if a.device == "chip"]
    assert host.m < min(chip.chunk_rows)
    return host.m * row_bytes


@pytest.mark.parametrize("m, r0, threads, order", [
    (37, 0, 4, "C"),          # rows not divisible by the thread count
    (1, 0, 4, "C"),           # one row: one thread
    (50, 13, 3, "C"),         # rows land at an offset into C
    (29, 5, 4, "F"),          # F-ordered rows
    (64, 0, 8, "C"),
], ids=["uneven", "one-row", "offset", "f-ordered", "even"])
def test_store_rows_gives_the_same_c_as_one_copy(monkeypatch, cpus,
                                                 fresh_pool, m, r0, threads,
                                                 order):
    monkeypatch.setattr(hgemms, "_STORE_BLOCK_FLOOR", 1)
    cpus(threads)
    rng = np.random.default_rng(m)
    rows = np.asarray(rng.standard_normal((m, 24), np.float32), order=order)
    c = np.zeros((r0 + m + 3, 24), np.float32)
    want = c.copy()
    want[r0:r0 + m] = rows
    used = hgemms._store_rows(c, r0, rows)
    assert used == min(threads, m)
    assert np.array_equal(c, want)


def test_a_store_under_the_block_floor_runs_on_the_calling_thread(
        monkeypatch, fresh_pool):
    ran_on = []
    copy = hgemms._copy_rows

    def record(c, r0, rows):
        ran_on.append(threading.current_thread())
        copy(c, r0, rows)

    monkeypatch.setattr(hgemms, "_copy_rows", record)
    rows = np.ones((256, 1024), np.float32)          # 1 MiB, under 64 MiB
    c = np.zeros((300, 1024), np.float32)
    assert hgemms._store_rows(c, 10, rows) == 1
    assert ran_on == [threading.current_thread()]
    assert hgemms._store_pool is None
    assert np.array_equal(c[10:266], rows)


def test_store_threads_follows_size_cpus_and_cap(monkeypatch, cpus):
    floor, cap = hgemms._STORE_BLOCK_FLOOR, hgemms._STORE_THREADS_CAP
    cpus(64)
    assert hgemms._store_threads(1000, floor // 1000) == 1
    assert hgemms._store_threads(1000, 3 * floor // 1000 + 1) == 4
    assert hgemms._store_threads(10 ** 6, floor) == cap
    assert hgemms._store_threads(2, 10 * floor) == 2   # never above the rows
    cpus(3)
    assert hgemms._store_threads(10 ** 6, floor) == 3
    assert hgemms._store_threads(0, floor) == 1


def test_a_block_that_raises_fails_the_store_after_every_block_ends(
        monkeypatch, cpus, fresh_pool):
    monkeypatch.setattr(hgemms, "_STORE_BLOCK_FLOOR", 1)
    cpus(4)
    done = []
    copy = hgemms._copy_rows

    def second_block_fails(c, r0, rows):
        if r0 == 10:
            raise MemoryError("block 1")
        copy(c, r0, rows)
        done.append(r0)

    monkeypatch.setattr(hgemms, "_copy_rows", second_block_fails)
    c = np.zeros((40, 8), np.float32)
    with pytest.raises(MemoryError, match="block 1"):
        hgemms._store_rows(c, 0, np.ones((40, 8), np.float32))
    assert sorted(done) == [0, 20, 30]


@pytest.mark.parametrize("pipeline_chunks", [None, 2],
                         ids=["one-chunk", "two-chunks"])
def test_a_block_that_raises_fails_the_job(monkeypatch, cpus, fresh_pool,
                                           pipeline_chunks):
    hg, a, b = _bound(pipeline_chunks=pipeline_chunks)
    monkeypatch.setattr(hgemms, "_STORE_BLOCK_FLOOR",
                        _chip_store_floor(hg, 256, 384, 128))
    cpus(4)
    copy = hgemms._copy_rows
    blocks = []

    def last_block_fails(c, r0, rows):
        blocks.append(threading.current_thread().name)
        if r0 + len(rows) == 256:
            raise MemoryError("store block")
        copy(c, r0, rows)

    monkeypatch.setattr(hgemms, "_copy_rows", last_block_fails)
    result = None
    with pytest.raises(MemoryError, match="store block"):
        result = hg.execute(a, b)
    assert result is None
    assert any(name.startswith("poas-store") for name in blocks)


@pytest.mark.parametrize("pipeline_chunks", [None, 2],
                         ids=["one-chunk", "two-chunks"])
def test_threaded_stores_give_the_same_c_and_spans_carry_threads(
        tmp_path, monkeypatch, cpus, fresh_pool, pipeline_chunks):
    """Chip stores split over threads, the host's store stays one copy; C is
    bit for bit what one copy per partition gives, and each store span reads
    the threads its store took."""
    m, n, k = 256, 384, 128
    hg, a, b = _bound(pipeline_chunks=pipeline_chunks, m=m, n=n, k=k)
    c_one, _ = hg.execute(a, b)           # one copy a store; compiles
    monkeypatch.setattr(hgemms, "_STORE_BLOCK_FLOOR",
                        _chip_store_floor(hg, m, n, k))
    cpus(4)
    with jax.profiler.trace(str(tmp_path)):
        c, rep = hg.execute(a, b)
    assert np.array_equal(c, c_one)
    np.testing.assert_array_equal(c, a.astype(np.float64) @ b.astype(np.float64))
    spans = _host_spans(tmp_path)
    chip = [s for s in spans if s[0] == "poas.copy_out.store"]
    host = [s for s in spans if s[0] == "poas.compute.store"]
    assert len(chip) == (pipeline_chunks or 1)
    assert [s[3]["threads"] for s in chip] == [4] * len(chip)
    assert [s[3]["device"] for s in host] == ["host"]
    assert host[0][3]["threads"] == 1
    # the store phase is still one interval on the stage's own thread
    for ev in rep.measured.events:
        names = [name for name, _, _ in ev.phases]
        assert names.count("store") <= 1


def test_the_store_pool_is_made_once_across_jobs(monkeypatch, cpus,
                                                 fresh_pool):
    made = []

    class Counting(hgemms.ThreadPoolExecutor):
        def __init__(self, *args, **kwargs):
            made.append(self)
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(hgemms, "ThreadPoolExecutor", Counting)
    hg, a, b = _bound()
    monkeypatch.setattr(hgemms, "_STORE_BLOCK_FLOOR",
                        _chip_store_floor(hg, 256, 384, 128))
    cpus(4)
    assert hgemms._store_pool is None     # nothing made before a store
    want = a.astype(np.float64) @ b.astype(np.float64)
    for _ in range(3):
        c, _ = hg.execute(a, b)
        np.testing.assert_array_equal(c, want)
    assert len(made) == 1
    assert hgemms._store_pool is made[0]
    store_threads = [t for t in threading.enumerate()
                     if t.name.startswith("poas-store")]
    assert 1 < len(store_threads) <= hgemms._STORE_THREADS_CAP
