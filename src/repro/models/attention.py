"""Attention: GQA/MQA with RoPE (full/partial), QK-norm, sliding-window (local)
masks, chunked online-softmax for long prefill, and KV-cache decode.

All four projections run through the quantized linear (paper Fig. 3 applies
the scheme to every linear layer); the softmax itself stays fp32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.linear import qlinear
from repro.models.blocks import rmsnorm, site_seed

NEG_INF = -1e30
# plain (materialized-scores) attention below this sequence length; chunked
# online-softmax above (prefill_32k would otherwise materialize S^2 scores).
CHUNK_THRESHOLD = 8192
Q_BLOCK = 1024
KV_BLOCK = 1024


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_tables(positions: jax.Array, dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for `dim` rotary dims at given positions (…,)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., dim/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, fraction: float = 1.0):
    """Rotate the first `fraction` of head dims (chatglm3 uses 0.5, '2d' RoPE)."""
    hd = x.shape[-1]
    rot = int(hd * fraction)
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    c = cos[..., None, : rot // 2]
    s = sin[..., None, : rot // 2]
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out, xp], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# masks / SDPA
# --------------------------------------------------------------------------

def _mask_bias(sq: int, sk: int, q_off, causal: bool, window: int | None):
    qi = q_off + jnp.arange(sq)[:, None]
    kj = jnp.arange(sk)[None, :]
    ok = jnp.ones((sq, sk), bool)
    if causal:
        ok &= kj <= qi
    if window is not None:
        ok &= kj > qi - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def sdpa(q, k, v, *, causal=True, window=None, q_off=0):
    """Plain SDPA. q: (B,Sq,H,hd), k: (B,Sk,KV,hd), v: (B,Sk,KV,vd)
    -> (B,Sq,H,vd). vd may differ from hd (MLA)."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    qf = q.reshape(b, sq, kv, rep, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bqgrh,bkgh->bgrqk", qf, kf) / jnp.sqrt(hd)
    scores = scores + _mask_bias(sq, k.shape[1], q_off, causal, window)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bgrqk,bkgv->bqgrv", p, vf)
    return o.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def chunked_sdpa(q, k, v, *, causal=True, window=None):
    """Online-softmax attention over KV blocks (flash-style, inference paths).

    Never materializes (Sq, Sk) scores: peak transient is (B, H, Q_BLOCK,
    KV_BLOCK) — the memory-roofline fix for prefill_32k.
    """
    b, sq, h, hd = q.shape
    vd = v.shape[-1]
    kv = k.shape[2]
    rep = h // kv
    sk = k.shape[1]
    nq, nk = sq // Q_BLOCK if sq >= Q_BLOCK else 1, max(sk // KV_BLOCK, 1)
    qb = Q_BLOCK if sq >= Q_BLOCK else sq
    kb = sk // nk
    qf = q.reshape(b, nq, qb, kv, rep, hd).astype(jnp.float32)
    kf = k.reshape(b, nk, kb, kv, hd).astype(jnp.float32)
    vf = v.reshape(b, nk, kb, kv, vd).astype(jnp.float32)

    def q_block(qi, qblk):
        q_off = qi * qb

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk = kf[:, ki]
            vblk = vf[:, ki]
            s = jnp.einsum("bqgrh,bkgh->bgrqk", qblk, kblk) / jnp.sqrt(hd)
            s = s + _mask_bias(qb, kb, q_off - ki * kb, causal, window)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bgrqk,bkgv->bgrqv", p, vblk)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, kv, rep, qb), NEG_INF, jnp.float32),
                jnp.zeros((b, kv, rep, qb), jnp.float32),
                jnp.zeros((b, kv, rep, qb, vd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return o.transpose(0, 3, 1, 2, 4)  # (b, qb, kv, rep, hd)

    out = jax.lax.map(lambda qi: q_block(qi, qf[:, qi]), jnp.arange(nq))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, vd)
    return out.astype(q.dtype)


def attend(q, k, v, *, causal=True, window=None):
    if q.shape[1] > CHUNK_THRESHOLD or k.shape[1] > CHUNK_THRESHOLD:
        return chunked_sdpa(q, k, v, causal=causal, window=window)
    return sdpa(q, k, v, causal=causal, window=window)


def decode_sdpa(q, k_cache, v_cache, pos, window=None, abs_pos=None):
    """Decode attention over a cache. q: (B,Sq,H,hd); caches (B,Smax,KV,hd);
    pos (B,) is the absolute position of each row's FIRST query token (Sq > 1
    is a chunked-prefill step, Sq == 1 plain decode).

    `abs_pos` (B,Smax) optionally maps cache index -> absolute position for
    ring buffers (sliding-window caches that wrap); entries < 0 mean "never
    written". Default: cache index IS the absolute position.
    """
    from repro.core import linear as QL  # sharding hints (None off-mesh)
    b, sq, h, hd = q.shape
    kv = k_cache.shape[2]
    rep = h // kv
    sk = k_cache.shape[1]
    qf = q.reshape(b, sq, kv, rep, hd).astype(jnp.float32)
    # Perf iteration (decode): the KV cache shards head_dim over "model"; pin
    # q to the SAME hd sharding and the score layout to batch-DP so the
    # contraction lowers to a psum of (B,KV,rep,Sq,S) scores instead of
    # all-gathering the multi-GiB cache.
    qf = QL._hint(qf, (QL._dp(b), None, None, None, QL._tp(hd)))
    s = jnp.einsum("bqgrh,bkgh->bgrqk", qf, k_cache.astype(jnp.float32)) / jnp.sqrt(hd)
    s = QL._hint(s, (QL._dp(b), None, None, None, None))
    qpos = pos[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]  # (B,Sq)
    if abs_pos is None:
        kj = jnp.arange(sk, dtype=jnp.int32)[None, :]
    else:
        kj = abs_pos
    ok = kj[:, None, :] <= qpos[:, :, None]                          # (B,Sq,Sk)
    if abs_pos is not None:
        ok &= kj[:, None, :] >= 0
    if window is not None:
        ok &= kj[:, None, :] > qpos[:, :, None] - window
    s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrqk,bkgv->bqgrv", p, v_cache.astype(jnp.float32))
    return o.reshape(b, sq, h, v_cache.shape[-1]).astype(q.dtype)


def ring_abs_pos(pos, sq: int, cap: int):
    """Absolute position held by each ring-buffer slot after writing a chunk.

    With per-row last written position P = pos + sq - 1, slot j holds the
    largest position <= P congruent to j mod cap; negative results mean the
    slot was never written. Valid whenever cap == window (a slot's previous
    occupant is at least one full window older, so masking by query position
    is exact)."""
    pmax = pos + sq - 1
    j = jnp.arange(cap, dtype=jnp.int32)[None, :]
    return pmax[:, None] - ((pmax[:, None] - j) % cap)


# --------------------------------------------------------------------------
# GQA block
# --------------------------------------------------------------------------

def gqa_init(key, cfg):
    from repro.models.blocks import linear_init
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": linear_init(ks[0], cfg.n_heads * hd, cfg.d_model),
        "wk": linear_init(ks[1], cfg.n_kv_heads * hd, cfg.d_model),
        "wv": linear_init(ks[2], cfg.n_kv_heads * hd, cfg.d_model),
        "wo": linear_init(ks[3], cfg.d_model, cfg.n_heads * hd),
    }
    if cfg.qk_norm:
        p["qn"] = jnp.ones((hd,), jnp.float32)
        p["kn"] = jnp.ones((hd,), jnp.float32)
    return p


def _project_qkv(p, x, cfg, scheme, seed, layer, positions):
    b, s, _ = x.shape
    hd = cfg.hd
    q = qlinear(x, p["wq"], site_seed(seed, layer, 0), scheme).reshape(b, s, cfg.n_heads, hd)
    k = qlinear(x, p["wk"], site_seed(seed, layer, 1), scheme).reshape(b, s, cfg.n_kv_heads, hd)
    v = qlinear(x, p["wv"], site_seed(seed, layer, 2), scheme).reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["qn"], cfg.norm_eps)
        k = rmsnorm(k, p["kn"], cfg.norm_eps)
    if cfg.rope_fraction > 0:
        rot = int(hd * cfg.rope_fraction)
        cos, sin = rope_tables(positions, rot, cfg.rope_theta)
        q = apply_rope(q, cos, sin, cfg.rope_fraction)
        k = apply_rope(k, cos, sin, cfg.rope_fraction)
    return q, k, v


def gqa_apply(p, x, cfg, scheme, seed, layer, *, causal=True, window=None,
              positions=None):
    """Full-sequence GQA (train / prefill). Returns (out, (k, v)) so callers
    can populate a decode cache."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(p, x, cfg, scheme, seed, layer, positions)
    o = attend(q, k, v, causal=causal, window=window)
    out = qlinear(o.reshape(b, s, -1), p["wo"], site_seed(seed, layer, 3), scheme)
    return out, (k, v)


def gqa_decode(p, x, cfg, scheme, seed, layer, cache_kv, pos, *, window=None,
               active=None, block_table=None, paged_kernel=False,
               pool_layer=None):
    """Cached decode / chunked-prefill step. x: (B, Sq, D) with Sq >= 1.

    pos: scalar or (B,) — absolute position of each row's first token
      (per-sequence vector = ragged prompts / continuous batching).
    active: (B,) bool — rows whose cache may be written (inactive slots in a
      serving batch keep their cache bit-for-bit: writes are routed out of
      bounds and dropped).
    block_table: (B, MAXB) int32 — when given, cache_kv holds POOL-shaped
      (P, BS, KV, hd) leaves and reads/writes go through the paged KV pool
      (serve/kv_pool.py); unallocated entries carry the pool's OOB sentinel.
    pool_layer: with block_table, cache_kv holds the STACKED (L, P, BS, KV,
      hd) pool leaves and this layer's are `pool_layer` among them: the
      scatter and the kernel work on the stack in place, and the updated
      stack is returned (models/lm.py carries it through the layer scan).
    paged_kernel: attend with the block-table flash-decode Pallas kernel
      (kernels/paged_attention.py) instead of materializing gather_view
      copies — O(row length) HBM traffic instead of O(table capacity).

    Contract: this step is ROW-LOCAL (row b reads/writes only row b of x,
    positions, and the cache — shard-local block-table indices included),
    so the mesh-sharded serving engine may split the batch and pool across
    a shard_map "data" axis without changing a bit, and the sentinel is
    always derived from the (possibly shard-local) pool leaf itself
    (docs/CONVENTIONS.md §2-3).
    """
    b, sq = x.shape[:2]
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = posb[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv(p, x, cfg, scheme, seed, layer, positions)
    valid = positions >= 0
    if active is not None:
        valid &= active[:, None]
    if block_table is not None:
        from repro.serve import kv_pool as KV
        # reads resolve through the READ table; scatters go through the
        # WRITE view, whose prefix-cache-aliased entries hold the sentinel —
        # shared blocks are provably never written (docs/CONVENTIONS.md §5).
        # A plain (B, MAXB) table is its own write view.
        rt, wt = KV.split_tables(block_table)
        packed = isinstance(cache_kv, KV.PackedKVPair)
        if packed:   # NVFP4 pool: K and V quantize into one pair of leaves
            new_cache = KV.scatter_tokens(cache_kv, wt, positions, (k, v),
                                          valid, pool_layer)
        else:
            new_cache = tuple(
                KV.scatter_tokens(c, wt, positions, x, valid, pool_layer)
                for c, x in zip(cache_kv, (k, v)))
        if paged_kernel:
            # a packed pool goes to the kernel as its raw leaves, which it
            # dequantizes block-wise in VMEM (kernels/paged_attention.py)
            from repro.kernels import ops as KOPS
            kernel = KOPS.paged_attention_q if packed else KOPS.paged_attention
            o = kernel(q, *new_cache, rt, posb, pool_layer, window=window)
        else:
            # gather_view dequantizes packed pools to bf16 (exactly), so
            # the reference path is storage-mode agnostic
            views = (KV.gather_view(new_cache, rt, pool_layer) if packed else
                     [KV.gather_view(c, rt, pool_layer) for c in new_cache])
            o = decode_sdpa(q, *views, posb, window=window)
    else:
        kc, vc = cache_kv
        cap = kc.shape[1]
        ring = window is not None and cap == window
        if ring and sq > 1:
            # in-chunk ring writes evict keys still inside earlier chunk
            # queries' windows, and ring_abs_pos labels slots from the
            # chunk's LAST position only — correct solely for sq == 1
            raise NotImplementedError(
                "ring-buffer (cap == window) caches decode one token at a "
                "time; chunked prefill needs a full-capacity or paged cache")
        idx = positions % cap if ring else positions
        idx = jnp.where(valid, idx, cap)  # OOB index => scatter drops the row
        bi = jnp.arange(b)[:, None]
        kc = kc.at[bi, idx].set(k.astype(kc.dtype), mode="drop")
        vc = vc.at[bi, idx].set(v.astype(vc.dtype), mode="drop")
        abs_pos = ring_abs_pos(posb, sq, cap) if ring else None
        o = decode_sdpa(q, kc, vc, posb, window=window, abs_pos=abs_pos)
        new_cache = (kc, vc)
    if active is not None:
        # Inactive rows must not read cache memory: their stale contents are
        # layout-dependent (dense keeps retired sequences' K/V, the pool
        # reads zeros) and any nonzero garbage would leak into active rows
        # through the per-tensor activation-quantization absmax. Zeroing the
        # attention output makes inactive rows a pure function of their
        # (deterministic) token stream.
        o = o * active[:, None, None, None].astype(o.dtype)
    out = qlinear(o.reshape(b, sq, -1), p["wo"], site_seed(seed, layer, 3), scheme)
    return out, new_cache
