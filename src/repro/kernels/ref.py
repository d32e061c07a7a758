"""Pure-jnp oracles for every Pallas kernel (the correctness contract)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import formats as F
from repro.core import ms_eden as ME
from repro.core import quant as Q


def nvfp4_fos_quant_ref(x: jax.Array):
    """Oracle for kernels.nvfp4_quant.nvfp4_fos_quant."""
    qt = Q.quant_four_over_six(x)
    deq = Q.dequant(qt, jnp.bfloat16)
    return deq, qt.codes, qt.scales, qt.gscale


def ms_eden_requant_ref(x: jax.Array, rht_key: jax.Array, sr_key: jax.Array):
    """Oracle for kernels.ms_eden_requant (the two-phase post-hoc path)."""
    p1 = ME.ms_eden_phase1(x, jax.random.wrap_key_data(rht_key))
    qt = ME.ms_eden_phase2(p1, jax.random.wrap_key_data(sr_key))
    return qt.codes, qt.scales, qt.gscale


def paged_attention_ref(q, k_pool, v_pool, table, pos, *, window=None):
    """Oracle for kernels.ops.paged_attention: literally today's serving
    reference path — materialize gather_view(pool, table) and run
    decode_sdpa over the full table capacity."""
    from repro.models.attention import decode_sdpa
    from repro.serve.kv_pool import gather_view
    return decode_sdpa(q, gather_view(k_pool, table),
                       gather_view(v_pool, table),
                       jnp.asarray(pos, jnp.int32), window=window)


def paged_mla_attention_ref(q_abs, q_rope, cc_pool, kc_pool, table, pos, *,
                            qk_dim: int):
    """Oracle for kernels.ops.paged_mla_attention: the gathered-view
    absorbed-form score/readout einsums from models.mla.mla_decode
    (o_lat, fp32 — before the caller's W_uv absorption)."""
    from repro.models.attention import NEG_INF
    from repro.serve.kv_pool import gather_view
    cv = gather_view(cc_pool, table)
    kv = gather_view(kc_pool, table)
    posb = jnp.asarray(pos, jnp.int32)
    sq = q_abs.shape[1]
    positions = posb[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    s_lat = jnp.einsum("bqhl,btl->bhqt", q_abs.astype(jnp.float32),
                       cv.astype(jnp.float32))
    s_rope = jnp.einsum("bqhr,btr->bhqt", q_rope.astype(jnp.float32),
                        kv.astype(jnp.float32))
    s = (s_lat + s_rope) * (1.0 / jnp.sqrt(jnp.float32(qk_dim)))
    tmask = (jnp.arange(cv.shape[1], dtype=jnp.int32)[None, None, :]
             <= positions[:, :, None])
    s = jnp.where(tmask[:, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqt,btl->bqhl", prob, cv.astype(jnp.float32))


def paged_attention_q_ref(q, codes, scales, table, pos, *, window=None):
    """Oracle for kernels.ops.paged_attention_q: decode the packed
    `PackedKVPair` pool to bf16 K and V (exact — e2m1 x e4m3 products fit
    bf16) and run the bf16 reference path, so kernel and oracle see
    bit-identical operands."""
    from repro.serve.kv_pool import PackedKVPair, unpack_pair
    k, v = unpack_pair(PackedKVPair(codes, scales))
    return paged_attention_ref(q, k, v, table, pos, window=window)


def paged_mla_attention_q_ref(q_abs, q_rope, cc_codes, cc_scales, kc_codes,
                              kc_scales, table, pos, *, qk_dim: int):
    """Oracle for kernels.ops.paged_mla_attention_q (same decode-then-
    reference construction)."""
    from repro.core.formats import nvfp4_cache_decode
    return paged_mla_attention_ref(
        q_abs, q_rope, nvfp4_cache_decode(cc_codes, cc_scales),
        nvfp4_cache_decode(kc_codes, kc_scales), table, pos, qk_dim=qk_dim)


def fp4_matmul_ref(a_packed, a_scales, b_packed, b_scales, ga, gb):
    """Oracle for kernels.fp4_matmul."""
    def deq(p, s, g):
        codes = F.unpack_fp4(p)
        vals = F.fp4_decode(codes)
        return vals * jnp.repeat(s.astype(jnp.float32), F.GROUP, -1) * g
    a = deq(a_packed, a_scales, ga)
    b = deq(b_packed, b_scales, gb)
    return a @ b.T
