"""The plain reference: C = A @ B in float64 on the host, over sampled rows,
and the comparison that decides ``correct``.  It imports nothing of the
program under test."""
from __future__ import annotations

import numpy as np


def sample_rows(partitions, seed: int, per_partition: int) -> np.ndarray:
    """Rows to compare: the first and last row of every partition (where
    partitions meet) and ``per_partition - 2`` more drawn from ``seed``
    inside each.  ``partitions`` is a list of (row0, rows)."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
    picked = []
    for row0, rows in partitions:
        if rows <= 0:
            continue
        inner = rng.integers(row0, row0 + rows,
                             size=max(0, per_partition - 2))
        picked.append(np.concatenate([[row0, row0 + rows - 1], inner]))
    return np.unique(np.concatenate(picked)).astype(np.int64)


def bf16_to_f64(x: np.ndarray) -> np.ndarray:
    """Exact widening of bfloat16 (the top half of a float32)."""
    bits = x.view(np.uint16).astype(np.uint32) << 16
    return bits.view(np.float32).astype(np.float64)


def reference(a: np.ndarray, b: np.ndarray, rows: np.ndarray, *,
              block: int = 2048) -> np.ndarray:
    """float64 C[rows] = A[rows] @ B, B widened a column block at a time so
    the reference fits in host memory beside the operands.  Every product of
    two bfloat16 values is exact in float64."""
    a64 = bf16_to_f64(a[rows])
    out = np.empty((len(rows), b.shape[1]))
    for j in range(0, b.shape[1], block):
        out[:, j:j + block] = a64 @ bf16_to_f64(
            np.ascontiguousarray(b[:, j:j + block]))
    return out


def max_rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """max |got - ref| over max |ref|: the widest gap of any compared element,
    in units of the largest reference element.  NaN compares as infinite."""
    gap = np.abs(got.astype(np.float64) - ref)
    if not np.all(np.isfinite(gap)):
        return float("inf")
    return float(gap.max() / np.abs(ref).max())
