"""Jit'd public wrappers for the Pallas kernels.

``interpret`` is the caller's choice and is never inferred: the chip path
passes nothing (native Mosaic kernels), CPU tests pass ``interpret=True``
(Python-interpreted, bit-accurate).
"""
from __future__ import annotations

from functools import partial

import jax

from .flash_attention import flash_attention_pallas
from .matmul import matmul_pallas


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                   "out_dtype", "interpret"))
def matmul(a, b, *, block_m: int = 256, block_n: int = 256,
           block_k: int = 512, out_dtype=None, interpret: bool = False):
    return matmul_pallas(a, b, block_m=block_m, block_n=block_n,
                         block_k=block_k, out_dtype=out_dtype,
                         interpret=interpret)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)
