"""Seeded random weights for the dense GQA / SwiGLU configurations.

One function makes a layer's float32 weights from its key; the harness
packs them into the program's serving format (`prequantize` applied to one
layer at a time inside one jitted scan, so the float32 image of the model
never exists whole), and the plain references regenerate the same layer
from the same key. Linear weights are normal with std 1/sqrt(fan_in),
embedding and head normal with std 0.02, norm gains 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# what serving stores the GEMM weights that the scheme keeps in floating
# point, the embedding and the head in
SERVED_DTYPE = "bfloat16"


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 2**62 (wider than 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def keys(seed: int):
    k = seed_key(seed)
    return {"embed": jax.random.fold_in(k, 0), "head": jax.random.fold_in(k, 1),
            "layers": jax.random.fold_in(k, 2)}


def _linear(key, n_out: int, n_in: int) -> jax.Array:
    return jax.random.normal(key, (n_out, n_in), jnp.float32) * n_in ** -0.5


def layer_f32(key, m: dict) -> dict:
    """One layer in the program's tree layout: {"mix": {wq, wk, wv, wo},
    "n1": {g}, "ff": {wi, wo, wg}, "n2": {g}}."""
    d, hd = m["d_model"], m["head_dim"]
    ks = jax.random.split(key, 7)
    ones = jnp.ones((d,), jnp.float32)
    return {"mix": {"wq": _linear(ks[0], m["n_heads"] * hd, d),
                    "wk": _linear(ks[1], m["n_kv_heads"] * hd, d),
                    "wv": _linear(ks[2], m["n_kv_heads"] * hd, d),
                    "wo": _linear(ks[3], d, m["n_heads"] * hd)},
            "n1": {"g": ones},
            "ff": {"wi": _linear(ks[4], m["d_ff"], d),
                   "wo": _linear(ks[5], d, m["d_ff"]),
                   "wg": _linear(ks[6], m["d_ff"], d)},
            "n2": {"g": ones}}


def layer_key(seed: int, layer: int) -> jax.Array:
    return jax.random.fold_in(keys(seed)["layers"], layer)


def embed_head(seed: int, m: dict):
    k = keys(seed)
    shape = (m["vocab"], m["d_model"])
    return (jax.random.normal(k["embed"], shape, jnp.float32) * 0.02,
            jax.random.normal(k["head"], shape, jnp.float32) * 0.02)


def make_params(seed: int, m: dict, pack=None,
                embed_dtype: str = "float32", out_shardings=None) -> dict:
    """The whole model in the program's `lm.init` tree, made on the device
    in one jitted call. `pack(layer) -> layer` (the program's packer)
    turns each layer into its served type before the next is made; the
    embedding and head are stored in `embed_dtype`. `out_shardings`, a
    tree of shardings like the result's, makes each leaf on its shards
    (the same values: threefry is partitionable); None makes the model on
    the default device."""
    k = keys(seed)

    def build():
        def body(_, i):
            layer = layer_f32(jax.random.fold_in(k["layers"], i), m)
            return None, (pack(layer) if pack is not None else layer)
        _, stack = jax.lax.scan(body, None, jnp.arange(m["n_layers"]))
        embed, head = (w.astype(embed_dtype) for w in embed_head(seed, m))
        return {"embed": embed, "stages": [{"l0": stack}],
                "final_norm": {"g": jnp.ones((m["d_model"],), jnp.float32)},
                "head": head}

    if out_shardings is None:
        return jax.jit(build)()
    return jax.jit(build, out_shardings=out_shardings)()
