"""Plain reference of quartet2 pre-training steps (forward, backward, AdamW).

The forward is ref_dense's layer in float32 over the whole batch, with the
activation's tensor scale spanning the batch as the program's does. The
backward is what quartet2's unbiased backward quantizers estimate: the
straight-through gradient of the quantized forward, dX = E Q(W) and
dW = E^T Q(X), computed exactly. Then gradient clipping by global norm
and AdamW with warmup-cosine learning rate, as the configuration states.

`dtype` is the precision of everything the configuration keeps in float32
(weights, gradients, optimizer moments, activations): float32 for the
reference, bfloat16 for the lower-precision control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.lib import nvfp4, ref_dense, weights
from bench.lib.check_train import leaf_norms


@jax.custom_vjp
def qlinear(x, w):
    return nvfp4.four_over_six(x) @ nvfp4.four_over_six(w).T


def _qlinear_fwd(x, w):
    qx, qw = nvfp4.four_over_six(x), nvfp4.four_over_six(w)
    like = (jnp.zeros((), x.dtype), jnp.zeros((), w.dtype))
    return qx @ qw.T, (qx, qw, like)


def _qlinear_bwd(res, e):
    qx, qw, (x_like, w_like) = res
    e = e.astype(jnp.float32)
    return ((e @ qw).astype(x_like.dtype),
            (e.reshape(-1, e.shape[-1]).T @ qx.reshape(-1, qx.shape[-1])
             ).astype(w_like.dtype))


qlinear.defvjp(_qlinear_fwd, _qlinear_bwd)

Q_CHUNK = 512


def attention(q, k, v):
    """Causal GQA attention over (B, S, H, hd), queries in chunks so the
    score matrix never exists whole."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    chunk = min(Q_CHUNK, s)

    @jax.checkpoint
    def block(i):
        qc = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk, 1)
        qg = qc.reshape(b, chunk, kv, h // kv, hd)
        sc = jnp.einsum("bqgrh,bkgh->bgrqk", qg, k) / jnp.sqrt(
            jnp.asarray(hd, q.dtype))
        qi = i * chunk + jnp.arange(chunk)[:, None]
        sc = jnp.where(qi >= jnp.arange(s)[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc.astype(jnp.float32), -1).astype(q.dtype)
        return jnp.einsum("bgrqk,bkgh->bqgrh", p, v).reshape(b, chunk, h * hd)

    out = jax.lax.map(block, jnp.arange(s // chunk))
    return out.transpose(1, 0, 2, 3).reshape(b, s, h * hd)


def _layer(x, w, m, act):
    b, s, _ = x.shape
    hd = m["head_dim"]
    pos = jnp.arange(s)
    h = act(ref_dense.rmsnorm(x, w["n1"]["g"], m["norm_eps"]))
    q = qlinear(h, w["mix"]["wq"]).reshape(b, s, m["n_heads"], hd)
    k = qlinear(h, w["mix"]["wk"]).reshape(b, s, m["n_kv_heads"], hd)
    v = act(qlinear(h, w["mix"]["wv"]).reshape(b, s, m["n_kv_heads"], hd))
    q = act(ref_dense.rope(q, pos, m["rope_theta"]))
    k = act(ref_dense.rope(k, pos, m["rope_theta"]))
    x = act(x + qlinear(act(attention(q, k, v)), w["mix"]["wo"]))
    h = act(ref_dense.rmsnorm(x, w["n2"]["g"], m["norm_eps"]))
    a = act(jax.nn.silu(qlinear(h, w["ff"]["wi"])) * qlinear(h, w["ff"]["wg"]))
    return act(x + qlinear(a, w["ff"]["wo"]))


def loss(params, batch, m, dtype):
    act = (lambda a: a.astype(dtype))
    x = act(params["embed"][batch["tokens"]])
    stack = params["stages"][0]["l0"]
    for i in range(m["n_layers"]):
        w = jax.tree.map(lambda a: a[i], stack)
        x = jax.checkpoint(_layer, static_argnums=(2, 3))(x, w, m, act)
    x = ref_dense.rmsnorm(x, params["final_norm"]["g"], m["norm_eps"])
    logits = (x @ params["head"].T).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def lr_at(step: int, t: dict) -> float:
    """Linear warmup over the first tenth of the run, then cosine to 0."""
    warm = max(int(t["total_steps"] * 0.1), 1)
    if step < warm:
        return t["base_lr"] * step / warm
    prog = min(max((step - warm) / max(t["total_steps"] - warm, 1), 0.0), 1.0)
    return t["base_lr"] * 0.5 * (1 + math.cos(math.pi * prog))


@functools.partial(jax.jit, static_argnames=("mkey", "dtype"))
def _grad(params, batch, *, mkey, dtype):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params, batch, dict(mkey),
                                        jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnames=("dtype", "clip", "wd"))
def _adamw(params, mu, nu, grads, step, lr, *, dtype, clip, wd):
    b1, b2, eps = 0.9, 0.95, 1e-8
    f32 = jnp.float32
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(f32) ** 2)
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: (g.astype(f32) * scale).astype(dtype),
                         grads)
    t = (step + 1).astype(f32)

    def upd(g, m_, v_, p):
        m_ = (b1 * m_ + (1 - b1) * g).astype(dtype)
        v_ = (b2 * v_ + (1 - b2) * g * g).astype(dtype)
        delta = (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps) \
            + wd * p
        return m_, v_, (p - lr * delta).astype(dtype)

    out = jax.tree.map(upd, grads, mu, nu, params)
    pick = (lambda i: jax.tree.map(lambda o: o[i], out,
                                   is_leaf=lambda x: isinstance(x, tuple)))
    return pick(2), pick(0), pick(1), grads


def run(m: dict, seed: int, t: dict, batches: list, dtype="float32") -> dict:
    """Losses of the first len(batches) steps, the clipped first gradient's
    norm per leaf, and per leaf the norm of the parameters' change over
    all the steps."""
    ref_dense.check_arch(m)
    mkey = tuple(sorted(m.items()))
    p0 = weights.make_params(seed, m)
    params = jax.tree.map(lambda a: a.astype(dtype), p0)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, g1 = [], None
    for i, batch in enumerate(batches):
        loss_i, grads = _grad(params, batch, mkey=mkey, dtype=dtype)
        losses.append(float(loss_i))
        params, mu, nu, clipped = _adamw(
            params, mu, nu, grads, jnp.int32(i), lr_at(i, t), dtype=dtype,
            clip=t["grad_clip"], wd=t["weight_decay"])
        if i == 0:
            g1 = leaf_norms(clipped)
        del grads, clipped
    change = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b, params, p0))
    return {"loss": losses, "grad": g1, "change": change}
