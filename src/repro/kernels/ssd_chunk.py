"""Mamba-2 SSD intra-chunk Pallas kernel.

The SSD chunked algorithm (arXiv:2405.21060 §6) splits into an inter-chunk
recurrence (cheap, O(S/Q)) and an **intra-chunk quadratic part** — the
compute hot spot this kernel fuses:

    CB[q,t]  = C_q · B_t                      (Q×Q matmul on the MXU)
    L[q,t]   = exp(cum_q − cum_t) · 1[q ≥ t]  (decay mask, on the VPU)
    y[q]     = Σ_t (CB·L)[q,t] · (dt·x)[t]    (second MXU matmul)
    state    = Σ_t exp(cum_end − cum_t) · B_t ⊗ (dt·x)[t]

One grid cell = one (batch, head, chunk); all four stages stay in VMEM —
the (Q,Q) score tile never touches HBM.  ``ref.ssd_chunk_ref`` is the
pure-jnp oracle (also what `repro.models.ssm.ssd_scan` computes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _ssd_chunk_kernel(xdt_ref, b_ref, c_ref, cum_ref, y_ref, state_ref):
    xdt = xdt_ref[...].astype(F32)        # (Q, hp)   x * dt
    bmat = b_ref[...].astype(F32)         # (Q, ds)
    cmat = c_ref[...].astype(F32)         # (Q, ds)
    cum = cum_ref[...].astype(F32)        # (Q, 1)    within-chunk cumsum(dtA)

    q = xdt.shape[0]
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=F32)      # (Q, Q)
    diff = cum - cum.reshape(1, q)                            # cum_q - cum_t
    qi = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    ti = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    decay = jnp.where(qi >= ti, jnp.exp(diff), 0.0)
    m = cb * decay
    y_ref[...] = jax.lax.dot_general(
        m, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=F32).astype(y_ref.dtype)       # (Q, hp)

    # chunk state: Σ_t exp(cum_end - cum_t) B_t ⊗ xdt_t   -> (ds, hp)
    seg_end = cum[q - 1:q, :]                                 # (1, 1)
    w = jnp.exp(seg_end - cum)                                # (Q, 1)
    state_ref[...] = jax.lax.dot_general(
        bmat * w, xdt, (((0,), (0,)), ((), ())),
        preferred_element_type=F32).astype(state_ref.dtype)   # (ds, hp)


def ssd_chunk_pallas(xdt: jax.Array, B: jax.Array, C: jax.Array,
                     cum: jax.Array, *, interpret: bool = False
                     ) -> tuple[jax.Array, jax.Array]:
    """Intra-chunk SSD for all (batch, chunk, head) cells.

    xdt: (b, NC, Q, nh, hp)    B, C: (b, NC, Q, G, ds)   cum: (b, NC, Q, nh)
    Returns y_intra: (b, NC, Q, nh, hp) and states: (b, NC, nh, hp->?, ds)
    laid out as (b, NC, nh, ds, hp) to match the kernel's natural output.
    """
    b, nc, Q, nh, hp = xdt.shape
    G, ds = B.shape[3], B.shape[4]
    hg = nh // G

    xdt_t = xdt.transpose(0, 1, 3, 2, 4)      # (b, NC, nh, Q, hp)
    b_t = B.transpose(0, 1, 3, 2, 4)          # (b, NC, G, Q, ds)
    c_t = C.transpose(0, 1, 3, 2, 4)
    cum_t = cum.transpose(0, 1, 3, 2)[..., None]  # (b, NC, nh, Q, 1)

    grid = (b, nc, nh)
    y, st = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, None, Q, hp),
                         lambda i, j, h: (i, j, h, 0, 0)),
            pl.BlockSpec((None, None, None, Q, ds),
                         lambda i, j, h: (i, j, h // hg, 0, 0)),
            pl.BlockSpec((None, None, None, Q, ds),
                         lambda i, j, h: (i, j, h // hg, 0, 0)),
            pl.BlockSpec((None, None, None, Q, 1),
                         lambda i, j, h: (i, j, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, None, Q, hp),
                         lambda i, j, h: (i, j, h, 0, 0)),
            pl.BlockSpec((None, None, None, ds, hp),
                         lambda i, j, h: (i, j, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, nh, Q, hp), xdt.dtype),
            jax.ShapeDtypeStruct((b, nc, nh, ds, hp), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(xdt_t, b_t, c_t, cum_t)
    return y.transpose(0, 1, 3, 2, 4), st
