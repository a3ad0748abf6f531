"""Pallas TPU kernels for the perf-critical compute layers.

* ``matmul``          — MXU-tiled GEMM (the paper's domain; hgemms per-device
                        compute unit)
* ``flash_attention`` — causal/windowed GQA flash attention
* ``ssd_chunk``       — Mamba-2 SSD intra-chunk (the SSD quadratic hot spot)

Each has a pure-jnp oracle in ``ref.py``; kernels are validated in
interpret mode on CPU (see tests/test_kernels_*.py) and run natively on TPU.
Interpret mode is always the caller's explicit choice.
"""
from .ops import flash_attention, matmul
from .ssd_chunk import ssd_chunk_pallas
from . import ref

__all__ = ["flash_attention", "matmul", "ssd_chunk_pallas", "ref"]
