"""GPipe-style pipeline parallelism over a `shard_map`-ed "pipe" mesh axis.

Each device holds one contiguous stage of layers; microbatches stream through
the ring via `ppermute`. The schedule is the classic GPipe fill-drain: with S
stages and M microbatches the pipe runs M + S - 1 ticks, of which S - 1 are
bubble — `bubble_fraction` below, the quantity the launch cost model charges.

Stage boundaries optionally compress activations to NVFP4 before the hop
(`compress=True`): the wire payload becomes 4.5 bits/element (packed codes +
e4m3 group scales), the same format the gradient compression uses. Boundary
compression is deterministic RTN — serving-style forward-only traffic, no
unbiasedness requirement.

Runs under a `jax.shard_map` that is manual over every mesh axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quant as Q


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Idle fraction of the GPipe schedule: (S-1) / (M + S - 1)."""
    if n_stages <= 1:
        return 0.0
    return (n_stages - 1) / (n_micro + n_stages - 1)


def stack_stage_params(params, n_stages: int):
    """Split every leaf's leading (layers) axis into (n_stages, per_stage).

    The result feeds `shard_map` with in_spec P("pipe") so each device
    receives its own stage's layer stack.
    """
    def one(x):
        n = x.shape[0]
        assert n % n_stages == 0, (x.shape, n_stages)
        return x.reshape(n_stages, n // n_stages, *x.shape[1:])

    return jax.tree.map(one, params)


def _compress_boundary(y: jax.Array) -> jax.Array:
    """Round-trip a stage boundary through NVFP4 (simulated 4.5-bit wire)."""
    flat = y.reshape(y.shape[0], -1)
    qt = Q.quant_rtn(flat, s=Q.S_EDEN)
    return Q.dequant(qt, jnp.float32).reshape(y.shape).astype(y.dtype)


def gpipe(stage_fn, n_stages: int, n_micro: int, compress: bool = False):
    """Build the per-device GPipe body for `shard_map`.

    stage_fn(w, x) applies one stage. The returned `run(ws, xs)` expects
    `ws` sharded P("pipe") (leading stage axis, one stage per device) and
    `xs` replicated with a leading (n_micro,) axis; it returns the
    replicated (n_micro, ...) outputs of the final stage.
    """

    def run(ws, xs):
        stage = jax.lax.axis_index("pipe")
        w = jax.tree.map(lambda x: x[0], ws)  # this device's stage params
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        m = n_micro

        def tick(carry, t):
            recv, outs = carry
            mb = t - stage  # microbatch this stage works on at tick t
            inp = jnp.where(stage == 0, xs[jnp.clip(t, 0, m - 1)], recv)
            y = stage_fn(w, inp)
            wire = _compress_boundary(y) if compress else y
            nxt = jax.lax.ppermute(wire, "pipe", perm)
            valid = (mb >= 0) & (mb < m) & (stage == n_stages - 1)
            slot = jnp.clip(mb, 0, m - 1)
            outs = outs.at[slot].set(jnp.where(valid, y, outs[slot]))
            return (nxt, outs), None

        init = (jnp.zeros_like(xs[0]), jnp.zeros_like(xs))
        (_, outs), _ = jax.lax.scan(tick, init, jnp.arange(m + n_stages - 1))
        # outputs live on the last stage only; replicate for out_specs=P()
        mine = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(mine, "pipe")

    return run
