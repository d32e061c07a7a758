"""NVFP4 gradient compression for data-parallel all-reduce.

Inside a `shard_map` over the DP axis, each device stochastically rounds its
local gradient shard to NVFP4 (packed 4-bit codes + e4m3 group scales on the
wire = 4.5 bits/element vs 32 for fp32) and the mean is taken over the psum
of the dequantized values. Q_SR is unbiased (paper Sec. 3.1), so the
compressed mean is an unbiased estimator of the exact mean — averaging over
seeds/steps converges to it, which is what keeps training unbiased end-to-end.

Per-device seeds derive from (caller seed, axis_index, leaf index): devices
must NOT share rounding randomness or the SR errors correlate and stop
averaging out across the reduce.

Callers enter through `jax.shard_map`, manual over the DP axis;
`tests/test_substrate.py` checks the compressed mean's accuracy and
unbiasedness on a simulated 4-device mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import formats as F
from repro.core import quant as Q


def _device_key(seed: jax.Array, axis_name: str, tag: int) -> jax.Array:
    key = jax.random.wrap_key_data(jnp.asarray(seed).astype(jnp.uint32))
    key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
    return jax.random.fold_in(key, tag)


def _sr_roundtrip(x: jax.Array, key: jax.Array) -> jax.Array:
    """Quantize one leaf to NVFP4 with SR and dequantize (simulated wire)."""
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % F.GROUP
    if pad:
        flat = jnp.pad(flat, (0, pad))
    qt = Q.quant_sr(flat[None, :], key)
    deq = Q.dequant(qt)[0]
    if pad:
        deq = deq[: x.size]
    return deq.reshape(x.shape)


def compressed_psum_mean(x: jax.Array, axis_name: str, seed: jax.Array,
                         tag: int = 0) -> jax.Array:
    """Unbiased NVFP4-compressed mean of `x` over `axis_name` (one leaf).

    Call inside shard_map; `seed` is a uint32[2] per-step seed shared by all
    devices (the device index is folded in here).
    """
    deq = _sr_roundtrip(x, _device_key(seed, axis_name, tag))
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    return (jax.lax.psum(deq, axis_name) / n).astype(x.dtype)


def compressed_grad_mean(grads, axis_name: str, seed: jax.Array):
    """Tree version of `compressed_psum_mean` for a gradient pytree.

    Leaves smaller than one scale group skip quantization (norm gains and
    biases — a few floats; compressing them saves nothing and the e4m3 scale
    overhead would exceed the payload).
    """
    leaves, treedef = jax.tree.flatten(grads)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    out = []
    for i, g in enumerate(leaves):
        if g.size < F.GROUP:
            out.append((jax.lax.psum(g.astype(jnp.float32), axis_name) / n)
                       .astype(g.dtype))
        else:
            deq = _sr_roundtrip(g, _device_key(seed, axis_name, i + 1))
            out.append((jax.lax.psum(deq, axis_name) / n).astype(g.dtype))
    return jax.tree.unflatten(treedef, out)
