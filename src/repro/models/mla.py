"""Multi-head Latent Attention (DeepSeek-V2/V3).

Train/prefill use the expanded form; decode uses the weight-absorbed form over
the compressed latent cache (kv_lora_rank + rope dims per position — the whole
point of MLA: the KV cache is ~576 floats/token instead of 2*H*hd).

All projections are quantized linears; the absorbed decode einsums are bf16
(inference path, not part of the paper's training recipe).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.linear import qlinear
from repro.models.attention import NEG_INF, apply_rope, attend, rope_tables
from repro.models.blocks import linear_init, rmsnorm, site_seed


def mla_init(key, cfg):
    m = cfg.mla
    h = cfg.n_heads
    ks = jax.random.split(key, 6)
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": linear_init(ks[0], m.q_lora_rank, cfg.d_model),
        "q_norm": jnp.ones((m.q_lora_rank,), jnp.float32),
        "wq_b": linear_init(ks[1], h * qk_dim, m.q_lora_rank),
        "wkv_a": linear_init(ks[2], m.kv_lora_rank + m.qk_rope_head_dim, cfg.d_model),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "wkv_b": linear_init(ks[3], h * (m.qk_nope_head_dim + m.v_head_dim), m.kv_lora_rank),
        "wo": linear_init(ks[4], cfg.d_model, h * m.v_head_dim),
    }


def _latent(p, x, cfg, scheme, seed, layer, positions):
    """Shared projections: per-head q (nope+rope), latent c, rotated k_rope."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    q = qlinear(rmsnorm(qlinear(x, p["wq_a"], site_seed(seed, layer, 0), scheme),
                        p["q_norm"], cfg.norm_eps),
                p["wq_b"], site_seed(seed, layer, 1), scheme).reshape(b, s, h, qk)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    kv = qlinear(x, p["wkv_a"], site_seed(seed, layer, 2), scheme)
    c = rmsnorm(kv[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = kv[..., m.kv_lora_rank:][:, :, None, :]  # (B,S,1,rope)
    cos, sin = rope_tables(positions, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope, cos, sin)
    return q_nope, q_rope, c, k_rope


def mla_apply(p, x, cfg, scheme, seed, layer, *, positions=None):
    """Expanded-form MLA (train / prefill). Returns (out, (c, k_rope)) for the
    latent decode cache."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q_nope, q_rope, c, k_rope = _latent(p, x, cfg, scheme, seed, layer, positions)
    kvb = qlinear(c, p["wkv_b"], site_seed(seed, layer, 3), scheme)
    kvb = kvb.reshape(b, s, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = kvb[..., : m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim:]
    # fold rope part into the head dim so standard SDPA applies
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (*k_rope.shape[:2], h, k_rope.shape[-1]))], axis=-1)
    o = attend(q_full, k_full, v, causal=True)
    out = qlinear(o.reshape(b, s, -1), p["wo"], site_seed(seed, layer, 4), scheme)
    return out, (c, k_rope[:, :, 0, :])


def mla_decode(p, x, cfg, scheme, seed, layer, cache, pos, *, active=None,
               block_table=None, paged_kernel=False, pool_layer=None):
    """Absorbed-form decode over the latent cache. x: (B, Sq, D), Sq >= 1
    (Sq > 1 = chunked prefill).

    cache = (c: (B,Smax,kv_lora), kr: (B,Smax,rope)) — or pool-shaped
    (P,BS,dim) leaves addressed through `block_table` (serve/kv_pool.py),
    or with `pool_layer` the stacked (L,P,BS,dim) leaves, worked on in
    place at that layer (see gqa_decode); with `paged_kernel` the
    score/readout runs in the block-table flash-decode Pallas kernel
    instead of over gather_view copies.
    pos: scalar or (B,) first-token position; active: (B,) write gate.
    score_h(t) = q_nope_h^T Wuk_h c_t + q_rope_h^T kr_t   (Wuk absorbed into q)
    out_h = (sum_t p_t c_t)^T Wuv_h                        (Wuv absorbed after)

    NOTE: wkv_b participates as a RAW bf16/f32 matrix here (absorbed einsums
    are not quantized GEMMs), so the quantize-once weight cache leaves it
    unpacked (see serve/prequant.py) and the serving sharding rules keep it
    replicated (dist/sharding.py).

    Contract: row-local like gqa_decode — the sharded engine splits batch
    and latent pools over a shard_map "data" axis (shard-local table
    indices), which must not change a bit (docs/CONVENTIONS.md §3).
    """
    m = cfg.mla
    b, sq = x.shape[:2]
    h = cfg.n_heads
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = posb[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    q_nope, q_rope, c_new, kr_new = _latent(p, x, cfg, scheme, seed, layer, positions)
    cc, kc = cache
    kr2 = kr_new[:, :, 0, :]
    valid = positions >= 0
    if active is not None:
        valid &= active[:, None]

    wkv_b = p["wkv_b"].reshape(h, m.qk_nope_head_dim + m.v_head_dim, m.kv_lora_rank)
    w_uk = wkv_b[:, : m.qk_nope_head_dim, :]     # (H, nope, lora)
    w_uv = wkv_b[:, m.qk_nope_head_dim:, :]      # (H, v, lora)
    q_abs = jnp.einsum("bqhn,hnl->bqhl", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))              # (B,Sq,H,lora)
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim

    if block_table is not None:
        from repro.serve import kv_pool as KV
        # read table for gathers/kernel, write view (aliased prefix entries
        # -> sentinel) for scatters — see gqa_decode / CONVENTIONS.md §5
        rt, wt = KV.split_tables(block_table)
        cc = KV.scatter_tokens(cc, wt, positions, c_new, valid, pool_layer)
        kc = KV.scatter_tokens(kc, wt, positions, kr2, valid, pool_layer)
        if paged_kernel:
            from repro.kernels import ops as KOPS
            if isinstance(cc, KV.PackedKV):
                # NVFP4 latent pools: kernel dequantizes in VMEM
                o_lat = KOPS.paged_mla_attention_q(
                    q_abs, q_rope, cc.codes, cc.scales, kc.codes, kc.scales,
                    rt, posb, pool_layer, qk_dim=qk_dim)
            else:
                o_lat = KOPS.paged_mla_attention(q_abs, q_rope, cc, kc,
                                                 rt, posb, pool_layer,
                                                 qk_dim=qk_dim)
            cv = None
        else:
            cv = KV.gather_view(cc, rt, pool_layer)
            kv = KV.gather_view(kc, rt, pool_layer)
    else:
        idx = jnp.where(valid, positions, cc.shape[1])  # OOB => write dropped
        bi = jnp.arange(b)[:, None]
        cc = cc.at[bi, idx].set(c_new.astype(cc.dtype), mode="drop")
        kc = kc.at[bi, idx].set(kr2.astype(kc.dtype), mode="drop")
        cv, kv = cc, kc

    if cv is not None:  # gathered-view / dense reference arithmetic
        s_lat = jnp.einsum("bqhl,btl->bhqt", q_abs, cv.astype(jnp.float32))
        s_rope = jnp.einsum("bqhr,btr->bhqt", q_rope.astype(jnp.float32),
                            kv.astype(jnp.float32))
        scale = 1.0 / jnp.sqrt(qk_dim)
        s = (s_lat + s_rope) * scale
        tmask = (jnp.arange(cv.shape[1], dtype=jnp.int32)[None, None, :]
                 <= positions[:, :, None])                    # (B,Sq,T)
        s = jnp.where(tmask[:, None], s, NEG_INF)
        prob = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhqt,btl->bqhl", prob, cv.astype(jnp.float32))
    o = jnp.einsum("bqhl,hvl->bqhv", o_lat, w_uv.astype(jnp.float32))
    if active is not None:
        # see gqa_decode: inactive rows must not read (layout-dependent)
        # stale cache memory — zero their attention output
        o = o * active[:, None, None, None].astype(o.dtype)
    out = qlinear(o.reshape(b, sq, -1).astype(x.dtype), p["wo"],
                  site_seed(seed, layer, 4), scheme)
    return out, (cc, kc)
