"""The NVFP4 forward quantizer the configurations state, written out plainly
for the references (it shares no code with the program).

Quartet II's forward pass (paper Sec. 5) quantizes both GEMM operands with
four-over-six round-to-nearest: E2M1 elements in groups of 16 along the
inner dimension, one E4M3 scale per group, one float32 scale per tensor.
The tensor scale puts the absolute maximum at s* x 4/6 x 448, with
s* = 6 x 16/17 / 0.93 (the MSE-optimal clipping grid, paper Sec. 3.3);
each group keeps whichever of the two grid placements, absmax -> s* or
absmax -> s* x 4/6, has the lower squared error (ties keep s*).
"""

from __future__ import annotations

import jax.numpy as jnp

GROUP = 16
E4M3_MAX = 448.0
S_STAR = 6.0 * (16.0 / 17.0) / 0.93


def round_e2m1(x):
    """Nearest E2M1 magnitude with the sign kept; a value halfway between
    two grid points goes to the one with the even mantissa bit (0, 1, 2,
    4 win their ties); beyond 6 clips to 6."""
    m = jnp.abs(x)
    q = jnp.where(m <= 0.25, 0.0, jnp.where(m < 0.75, 0.5, jnp.where(
        m <= 1.25, 1.0, jnp.where(m < 1.75, 1.5, jnp.where(
            m <= 2.5, 2.0, jnp.where(m < 3.5, 3.0, jnp.where(
                m <= 5.0, 4.0, 6.0)))))))
    return jnp.sign(x) * q


def round_e4m3(x):
    return jnp.clip(x, -E4M3_MAX, E4M3_MAX).astype(
        jnp.float8_e4m3fn).astype(jnp.float32)


def four_over_six(x, axes=None):
    """Quantize-dequantize x (float32) along its last axis. `axes` are the
    axes the tensor scale spans (all, by default)."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=axes is not None)
    gscale = amax / (S_STAR * 4.0 / 6.0 * E4M3_MAX)
    gscale = jnp.where(gscale == 0, 1.0, gscale)
    g = x.reshape(*x.shape[:-1], x.shape[-1] // GROUP, GROUP)
    gmax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
    gs = gscale if axes is None else gscale[..., None]

    def place(top):
        scale = round_e4m3(gmax / (gs * top))
        denom = scale * gs
        q = round_e2m1(g / jnp.where(denom == 0, 1.0, denom)) * denom
        return q, jnp.sum((q - g) ** 2, axis=-1, keepdims=True)

    q6, e6 = place(S_STAR)
    q4, e4 = place(S_STAR * 4.0 / 6.0)
    return jnp.where(e4 < e6, q4, q6).reshape(x.shape)
