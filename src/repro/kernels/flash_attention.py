"""Flash-attention Pallas TPU kernel (causal / sliding-window, GQA).

Online-softmax over KV blocks with the running (m, l, acc) triple held in
VMEM scratch.  Grid: (batch, q_heads, Sq/bq, Skv/bk) with the KV dimension
innermost ("arbitrary" semantics) so the accumulator carries across KV
steps.  Block shapes keep q/k/v tiles within VMEM and lane-align head_dim.

The pure-JAX oracle is ``ref.flash_attention_ref`` (also what the model
stack executes on CPU); this kernel is the TPU drop-in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  kv_steps: int, block_q: int, block_k: int, sq: int, skv: int,
                  causal: bool, window: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)             # (bq, d)
    k = k_ref[...].astype(jnp.float32)             # (bk, d)
    v = v_ref[...].astype(jnp.float32)             # (bk, dv)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = k_pos < skv
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _store():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           block_q: int = 512, block_k: int = 512,
                           scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D); k/v: (B, Skv, KH, D/Dv).  Returns (B, Sq, H, Dv).

    GQA: the q-head→kv-head mapping happens in the k/v index_maps, so no
    repeated K/V materialization.
    """
    B, Sq, H, D = q.shape
    _, Skv, KH, Dv = v.shape
    G = H // KH
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    pq, pk = (-Sq) % bq, (-Skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    SQ, SK = q.shape[1], k.shape[1]
    # layout: (B, H, S, D) blocks of (1, 1, bs, d)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    kv_steps = SK // bk

    out = pl.pallas_call(
        functools.partial(_flash_kernel, kv_steps=kv_steps, block_q=bq,
                          block_k=bk, sq=Sq, skv=Skv, causal=causal,
                          window=window, scale=scale),
        grid=(B, H, SQ // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((None, None, bk, Dv),
                         lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, Dv),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, SQ, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)
    if pq:
        out = out[:, :Sq]
    return out
