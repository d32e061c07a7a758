"""Plain float32 reference of the dense GQA / SwiGLU model (Granite, Yi).

Straightforward jax.numpy at "highest" matmul precision, no kernel, cache
or paging, run layer by layer with each layer's weights regenerated from
the seed (bench/lib/weights.py), so it takes nothing the program made.
Every linear layer computes Q(x) Q(W)^T with the four-over-six NVFP4
quantizer of the configuration's scheme (bench/lib/nvfp4.py); the
activation's tensor scale spans one sequence. The LM head stays
unquantized, as the scheme states. RoPE rotates interleaved pairs of head
features (the program's convention).

`act` is where the precision of activations is set: identity for the
reference, a rounding for the lower-precision control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.lib import nvfp4, weights

# the architecture this reference implements, as a `model` block states it
ARCH = {"family": "dense", "attn": "gqa", "mlp": "swiglu"}


def check_arch(m: dict) -> None:
    other = {k: m.get(k) for k, v in ARCH.items() if m.get(k) != v}
    if other:
        raise ValueError(f"the dense reference implements {ARCH}, not the "
                         f"model's {other}")


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, positions, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[..., None] * inv
    c, s = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _identity(a):
    return a


def fp8(a):
    """Round to float8_e4m3fn: the precision below bfloat16."""
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


ACT = {"f32": _identity, "fp8": fp8}


def served_weights(layer, quant: bool, dtype: str):
    """The layer's weights as the model computes with them: four-over-six
    NVFP4 of the float32 weights when the scheme quantizes (the program
    packs them from float32), else stored in `dtype`."""
    def one(w):
        if w.ndim != 2:
            return w
        if quant:
            return nvfp4.four_over_six(w)
        return w.astype(dtype).astype(jnp.float32)
    return jax.tree.map(one, layer)


def attention(q, k, v):
    """Causal GQA softmax attention, one sequence per batch row.
    q (n, S, H, hd), k/v (n, S, KV, hd) -> (n, S, H*hd)."""
    n, s, h, hd = q.shape
    kv = k.shape[2]
    qg = q.reshape(n, s, kv, h // kv, hd)
    sc = jnp.einsum("nqgrh,nkgh->ngrqk", qg, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("ngrqk,nkgh->nqgrh", p, v).reshape(n, s, h * hd)


def layer_apply(x, valid, w, m, act, quant=True):
    """One transformer layer on x (n, S, d); rows with valid False are held
    at zero so they never enter a tensor scale."""
    keep = valid[..., None].astype(x.dtype)

    def qlin(a, wq):
        return (nvfp4.four_over_six(a, axes=(1, 2)) if quant else a) @ wq.T

    n, s, _ = x.shape
    hd = m["head_dim"]
    pos = jnp.arange(s)
    h = act(rmsnorm(x, w["n1"]["g"], m["norm_eps"]) * keep)
    q = qlin(h, w["mix"]["wq"]).reshape(n, s, m["n_heads"], hd)
    k = qlin(h, w["mix"]["wk"]).reshape(n, s, m["n_kv_heads"], hd)
    v = act(qlin(h, w["mix"]["wv"]).reshape(n, s, m["n_kv_heads"], hd))
    q = act(rope(q, pos, m["rope_theta"]))
    k = act(rope(k, pos, m["rope_theta"]))
    o = act(attention(q, k, v) * keep)
    x = act((x + qlin(o, w["mix"]["wo"])) * keep)
    h = act(rmsnorm(x, w["n2"]["g"], m["norm_eps"]) * keep)
    a = act(jax.nn.silu(qlin(h, w["ff"]["wi"])) * qlin(h, w["ff"]["wg"]))
    return act((x + qlin(a, w["ff"]["wo"])) * keep)


@functools.partial(jax.jit, static_argnames=("mkey", "act", "quant",
                                             "dtype"))
def _layer_step(x, valid, key, *, mkey, act, quant, dtype):
    m = dict(mkey)
    w = served_weights(weights.layer_f32(key, m), quant, dtype)
    return layer_apply(x, valid, w, m, ACT[act], quant)


@functools.partial(jax.jit, static_argnames=("mkey", "act"))
def _embed(tokens, valid, embed, *, mkey, act):
    return ACT[act](embed[tokens] * valid[..., None])


@functools.partial(jax.jit, static_argnames=("mkey",))
def _logits(x, g, head, *, mkey):
    m = dict(mkey)
    return rmsnorm(x, g, m["norm_eps"]) @ head.T


def logits(m: dict, seed: int, tokens, valid, act: str = "f32", *,
           quant: bool, dtype: str):
    """Float32 logits (n, S, vocab) of right-padded sequences tokens (n, S).
    `quant`: the scheme quantizes GEMM operands to NVFP4; `dtype`: the
    type the weights are served in."""
    check_arch(m)
    mkey = tuple(sorted(m.items()))
    with jax.default_matmul_precision("highest"):
        embed, head = (w.astype(dtype).astype(jnp.float32)
                       for w in weights.embed_head(seed, m))
        x = _embed(tokens, valid, embed, mkey=mkey, act=act)
        del embed
        for layer in range(m["n_layers"]):
            x = _layer_step(x, valid, weights.layer_key(seed, layer),
                            mkey=mkey, act=act, quant=quant, dtype=dtype)
        return _logits(x, jnp.ones((m["d_model"],), jnp.float32), head,
                       mkey=mkey)
