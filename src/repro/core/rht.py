"""Seeded blocked Randomized Hadamard Transform (RHT).

The paper (App. A) uses rotation blocks of d=128 so the rotation can be
expressed as a plain GEMM (mma.m16n8k16 on Blackwell; the 128x128 MXU tile on
TPU — the same reformulation, which is why this maps 1:1 onto TPU hardware).
One random sign diagonal is drawn per (tensor, micro-batch) and shared across
all rotation blocks of the tensor, exactly matching the paper's
"identical rotations for every rotation group within a tensor per micro-batch".

RHT(x) = reshape(x, (..., d/b, b)) @ (diag(sign) @ H_b / sqrt(b))

Block size: 128 when the inner dim allows, otherwise the largest power-of-two
multiple of 16 dividing d (all model inner dims here are multiples of 16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats as F
from repro.core import scopes as SC


@functools.lru_cache(maxsize=None)
def hadamard(n: int) -> np.ndarray:
    """Sylvester Hadamard matrix of power-of-two size n, normalized 1/sqrt(n)."""
    assert n & (n - 1) == 0 and n > 0, f"Hadamard size must be a power of 2, got {n}"
    h = np.ones((1, 1), dtype=np.float32)
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return (h / np.sqrt(n)).astype(np.float32)


def block_size(d: int) -> int:
    """Largest power-of-two block in {16,32,64,128} dividing d (prefer 128)."""
    for b in (F.RHT_BLOCK, 64, 32, 16):
        if d % b == 0:
            return b
    raise ValueError(f"inner dim {d} is not a multiple of 16")


def sign_vector(key: jax.Array, b: int) -> jax.Array:
    """Random +-1 diagonal of length b."""
    return jax.random.rademacher(key, (b,), dtype=jnp.float32)


def rht(x: jax.Array, key: jax.Array, b: int | None = None) -> jax.Array:
    """Apply the blocked RHT along the last axis. Orthogonal; self-inverse up
    to the sign diagonal (inverse = rht_inv)."""
    d = x.shape[-1]
    b = b or block_size(d)
    with jax.named_scope(SC.RHT):
        s = sign_vector(key, b)
        hm = jnp.asarray(hadamard(b))
        xf = x.astype(jnp.float32).reshape(*x.shape[:-1], d // b, b)
        out = (xf * s) @ hm
        return out.reshape(x.shape)


def rht_inv(x: jax.Array, key: jax.Array, b: int | None = None) -> jax.Array:
    """Inverse blocked RHT (H^T then undo the sign diagonal)."""
    d = x.shape[-1]
    b = b or block_size(d)
    with jax.named_scope(SC.RHT):
        s = sign_vector(key, b)
        hm = jnp.asarray(hadamard(b))
        xf = x.astype(jnp.float32).reshape(*x.shape[:-1], d // b, b)
        out = (xf @ hm.T) * s
        return out.reshape(x.shape)
