"""MXU-tiled matmul Pallas kernel — the hgemms per-device compute unit.

The paper's case-study hot spot is GEMM; on TPU the per-partition sub-GEMM
produced by ``ops_to_mnk`` runs through this kernel.  Grid is (M/bm, N/bn,
K/bk) with a float32 VMEM accumulator; block shapes are chosen so that
(bm·bk + bk·bn + bm·bn) tiles fit VMEM and the MXU dims are multiples of
(8, 128) — exactly the paper's "hardware adjustments" transplanted to TPU
(DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(a: jax.Array, b: jax.Array, *,
                  block_m: int = 256, block_n: int = 256, block_k: int = 512,
                  out_dtype=None, interpret: bool = False) -> jax.Array:
    """C = A @ B with explicit VMEM tiling.  Shapes need not be multiples of
    the block sizes — inputs are zero-padded and the result cropped."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or jnp.promote_types(a.dtype, b.dtype)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    # MXU alignment: sublane multiples of 8, lane multiples of 128 where the
    # dims allow it.
    bm = max(8 * (bm // 8), min(bm, m)) if m >= 8 else m
    bn = max(128 * (bn // 128), min(bn, n)) if n >= 128 else n
    bk = max(128 * (bk // 128), min(bk, k)) if k >= 128 else k

    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    M, K = a.shape
    _, N = b.shape
    k_steps = K // bk

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    if pm or pn:
        out = out[:m, :n]
    return out
