"""A GEMM's least time on a chip, from its unpadded shape and the chip's
published peaks (``peaks.json``, keyed by ``device_kind``)."""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str, path: pathlib.Path = PEAKS) -> dict:
    """The peaks of one kind of chip.  A kind missing from the table is an
    error, never a default."""
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def gemm_flop(m: int, n: int, k: int) -> float:
    return 2.0 * m * n * k


def gemm_bytes(m: int, n: int, k: int, *, in_bytes: int = 2,
               out_bytes: int = 4) -> float:
    """HBM bytes the algorithm must move at least: A and B read once in the
    operand dtype, C written once in the output dtype."""
    return float(m * k + k * n) * in_bytes + float(m) * n * out_bytes


def least_time(m: int, n: int, k: int, peaks: dict) -> tuple[float, str]:
    """(seconds, bound): the larger of FLOP over peak FLOP/s and bytes over
    peak bytes/s, and which of the two it is."""
    compute = gemm_flop(m, n, k) / peaks["bf16_flop_per_s"]
    memory = gemm_bytes(m, n, k) / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
