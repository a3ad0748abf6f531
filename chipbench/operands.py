"""Seeded GEMM operands, made on a device from the seed and read back to the
host, where the program under test takes them from."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 32 bits at a time."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnames="shape")
def _uniform_bf16(key, *, shape):
    # uniform in [-1, 1) drawn in float32, then rounded: most values need all
    # 8 of bfloat16's significand bits, so an operand rounded to fp8 or int8
    # changes C visibly
    return jax.random.uniform(key, shape, jnp.float32, -1.0,
                              1.0).astype(jnp.bfloat16)


def make_operands(seed: int, m: int, n: int, k: int,
                  device: jax.Device) -> tuple[np.ndarray, np.ndarray]:
    """Host bfloat16 A (m x k) and B (k x n) from ``seed``, one at a time on
    ``device`` so that its memory holds one float32 operand at most."""
    out = []
    with jax.default_device(device):
        for key, shape in zip(jax.random.split(seed_key(seed)),
                              ((m, k), (k, n))):
            x = _uniform_bf16(key, shape=shape)
            out.append(np.asarray(x))
            x.delete()
    return out[0], out[1]
