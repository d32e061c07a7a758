"""Pallas TPU kernel: block-table flash-decode over the paged KV pool.

The serving hot path used to materialize `serve.kv_pool.gather_view` — a
dense (B, MAXB*BS, ...) copy of each layer's pool — and then score against
the FULL table capacity every step. This kernel consumes the pool-shaped
leaves directly:

  - the (B, MAXB) block table and the (B,) position vector are
    SCALAR-PREFETCHED; each grid cell's BlockSpec index map resolves
    `table[b, j]` on the fly, so the pipeline DMAs exactly one physical
    pool block per (row, logical-block) cell and no gathered view ever
    exists in HBM;
  - the grid is (batch row, logical KV block) with the block axis
    innermost; a per-row online-softmax accumulator (m, l, acc) lives in
    VMEM scratch across the block sweep (flash-decode);
  - blocks that cannot contribute are SKIPPED, not masked after the fact:
    OOB-sentinel table entries (unallocated / inactive rows), blocks
    entirely beyond the row's newest query position (causal), and — for
    sliding-window `lattn` layers — blocks entirely older than the OLDEST
    query's window. Skipped cells clamp their index map to the last pool
    block and predicate out the compute, so the fetch is a buffer revisit,
    not extra traffic;
  - per-key masking inside a live block comes from absolute positions
    (key block j covers positions [j*BS, (j+1)*BS)), matching
    `models.attention.decode_sdpa`'s `kj <= qpos` / window rules exactly.

Two variants share the online-softmax update:

  gqa  — q (B, Sq, H, hd) vs K/V pools (P, BS, KV, hd)/(P, BS, KV, vd);
         grouped heads (rep = H // KV) broadcast over each KV head.
  mla  — absorbed-form latent decode: q_abs (B, Sq, H, lora) and
         q_rope (B, Sq, H, rope) vs the SHARED cc (P, BS, lora) /
         kc (P, BS, rope) pools; the score is q_abs·cc + q_rope·kc and
         the value readout is over cc itself (vd == lora != hd), so the
         kernel returns o_lat for the caller's w_uv absorption.

Sq >= 1 supports the engine's (n_slots, spec_k+1) speculative verify
chunks; query s of row b sits at absolute position pos[b] + s. Outputs are
fp32; callers cast. Fully-masked rows (inactive slots: all-sentinel table)
produce exact zeros (l == 0 guard), mirroring the reference path's
gathered-zeros result.

Each variant also has a `_q` twin consuming the NVFP4-quantized pool
(`serve.kv_pool.PackedKV`): the packed-operand BlockSpecs DMA uint8 e2m1
code pairs (d/2 bytes) plus uint8 e4m3 scale bits (d/16 bytes) per block —
0.28125x the bf16 HBM bytes — and `_dequant_halves` decodes them
block-wise in VMEM (arithmetic e2m1/e4m3 decode, no gathers) into even and
odd feature halves, which meet the matching halves of q and of the output
(split and re-interleaved by XLA outside the kernel) in the SAME online
softmax sweep. Dequant is exact in f32, so `_q` kernel operands match the
gather-then-decode reference bit-for-bit; only the order in which the two
halves' dot products are summed differs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats as F

NEG_INF = -1e30  # matches models.attention.NEG_INF


def _decode_e2m1(codes):
    """E2M1 decode without a gather: value = sign * m0 * 2^e with the 3-bit
    magnitude split as (e2, m1); subnormal pair {0, 0.5} special-cased
    (same arithmetic as kernels/fp4_matmul.py)."""
    c = codes.astype(jnp.int32)
    sign = jnp.where((c >> 3) & 1, -1.0, 1.0)
    e = (c >> 1) & 0x3
    m = c & 0x1
    mag = jnp.where(e == 0, 0.5 * m,
                    (1.0 + 0.5 * m) * jnp.exp2((e - 1).astype(jnp.float32)))
    return sign * mag


def _decode_e4m3_bits(bits):
    """E4M3 (float8_e4m3fn) decode from raw uint8 bits, arithmetically:
    (1 + m/8) * 2^(e-7) for normals, m/8 * 2^-6 subnormals. Cache scales
    are absmax-derived (non-negative, <= 448), so the sign bit is 0 and
    the NaN encoding (e=15, m=7) is unreachable — no bitcast needed in
    the kernel body."""
    b = bits.astype(jnp.int32)
    e = (b >> 3) & 0xF
    m = (b & 0x7).astype(jnp.float32)
    return jnp.where(e == 0, m * (0.125 * 2.0 ** -6),
                     (1.0 + m * 0.125)
                     * jnp.exp2((e - 7).astype(jnp.float32)))


def _dequant_halves(codes, scale_bits):
    """Dequantize packed pool operands in VMEM without an interleave:
    uint8 e2m1 code pairs (..., R, d/2) + e4m3 scale bits (..., R, d/16)
    -> the EVEN and ODD feature halves (..., R, d/2) f32 (byte i holds
    features 2i in its low and 2i+1 in its high nibble). Callers contract
    each half against the matching half of q or of the output, so no
    lane-merging reshape reaches Mosaic (which refuses that shape cast).

    Both features of byte i fall in scale group i // 8; the scales are
    broadcast along the lanes by a 0/1 matmul (batched over a leading axis
    for 3-D operands). That is exact: e4m3 values are bf16-representable
    and each output sums one nonzero term. Every e2m1 x e4m3 product is
    f32 (and bf16) representable, so the halves hold bit-identical
    operands to the gather-path bf16 dequant."""
    c = codes.astype(jnp.int32)
    sc = _decode_e4m3_bits(scale_bits)
    g, half = sc.shape[-1], c.shape[-1]
    expand = (jax.lax.broadcasted_iota(jnp.int32, (g, half), 1)
              // (F.GROUP // 2)
              == jax.lax.broadcasted_iota(jnp.int32, (g, half), 0)
              ).astype(jnp.float32)
    if sc.ndim == 3:
        sc = jax.lax.dot_general(
            sc, jnp.broadcast_to(expand, (sc.shape[0], g, half)),
            (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
    else:
        sc = jnp.dot(sc, expand, preferred_element_type=jnp.float32)
    return _decode_e2m1(c & 0xF) * sc, _decode_e2m1(c >> 4) * sc


def _split_halves(x):
    """(..., d) -> [even features, odd features], the q-side match of
    `_dequant_halves` (done by XLA outside the kernel)."""
    return [x[..., 0::2], x[..., 1::2]]


def _interleave_halves(even, odd):
    """Inverse of `_split_halves` (done by XLA outside the kernel)."""
    return jnp.stack([even, odd], axis=-1).reshape(*even.shape[:-1], -1)


def _positions(p0, sq: int, bs: int, j):
    """(Sq, BS) absolute key/query position grids for grid cell (row, j)."""
    kj = j * bs + jax.lax.broadcasted_iota(jnp.int32, (sq, bs), 1)
    qpos = p0 + jax.lax.broadcasted_iota(jnp.int32, (sq, bs), 0)
    return kj, qpos


def _online_update(s, ok, m_ref, l_ref, acc_ref, vals):
    """One flash step: fold masked scores `s` (..., Sq-ish, BS) and the block
    values into the running (m, l, acc) scratch. `vals` maps probabilities
    (..., BS) -> the block's value contribution per feature part (acc_ref's
    leading axis), so the variants share the numerics (exp of masked lanes
    is forced to exactly 0, and a block that changes nothing multiplies the
    accumulators by exactly 1.0)."""
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    for i, v in enumerate(vals(p)):
        acc_ref[i] = acc_ref[i] * corr[..., None] + v
    m_ref[...] = m_new


def _gqa_sweep(table_ref, pos_ref, q_refs, o_refs, m_ref, l_ref, acc_ref,
               load_kv, *, kv: int, bs: int, sentinel: int,
               window: int | None, sqrt_hd: float):
    """Shared GQA flash sweep over feature parts: one part (the whole head
    dim) for the bf16 pool, two (even/odd halves) for the packed one.
    `load_kv()` yields the cell's f32 keys (KV, hd_i, BS) and values
    (KV, BS, vd_i) per part — a bf16 cast or a VMEM dequant — and scores
    sum the parts' dots, so both storage modes run the same softmax/value
    arithmetic."""
    b, j = pl.program_id(0), pl.program_id(1)
    sq, h = q_refs[0].shape[1], q_refs[0].shape[2]
    rep = h // kv

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p0 = pos_ref[b]
    pmax = p0 + sq - 1                      # newest query position in the row
    live = (table_ref[b, j] < sentinel) & (j * bs <= pmax)
    if window is not None:
        # skip blocks whose newest key predates even the OLDEST query's
        # window (older queries admit older keys, so p0 — not pmax — is
        # the skip horizon; partial overlap is masked per key below)
        live &= (j + 1) * bs - 1 > p0 - window

    @pl.when(live)
    def _block():
        ks, vs = load_kv()
        # grouped scores: (KV, Sq*rep, hd_i) x (KV, hd_i, BS) -> (KV, Sq*rep, BS)
        parts = []
        for q_ref, k in zip(q_refs, ks):
            q = q_ref[0].astype(jnp.float32)    # (Sq, H, hd_i)
            qg = q.reshape(sq, kv, rep, -1).transpose(1, 0, 2, 3)
            qg = qg.reshape(kv, sq * rep, -1)
            parts.append(jax.lax.dot_general(
                qg, k, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32))
        s = functools.reduce(jnp.add, parts) / sqrt_hd
        s = s.reshape(kv, sq, rep, bs)
        kj, qpos = _positions(p0, sq, bs, j)
        ok = kj <= qpos
        if window is not None:
            ok &= kj > qpos - window
        ok = ok[None, :, None, :]           # (1, Sq, 1, BS)

        def vals(p):                        # (KV, Sq, rep, BS) -> value sums
            pf = p.reshape(kv, sq * rep, bs)
            return [jax.lax.dot_general(
                pf, v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32
            ).reshape(kv, sq, rep, v.shape[-1]) for v in vs]

        _online_update(s, ok, m_ref, l_ref, acc_ref, vals)

    @pl.when(j == pl.num_programs(1) - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)[..., None]
        for i, o_ref in enumerate(o_refs):
            o = acc_ref[i] / l
            o_ref[0] = o.transpose(1, 0, 2, 3).reshape(sq, h, -1)


def _gqa_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                m_ref, l_ref, acc_ref, *, bs: int, sentinel: int,
                window: int | None, sqrt_hd: float):
    _gqa_sweep(table_ref, pos_ref, (q_ref,), (o_ref,), m_ref, l_ref, acc_ref,
               lambda: ([k_ref[0].astype(jnp.float32).transpose(1, 2, 0)],
                        [v_ref[0].astype(jnp.float32).transpose(1, 0, 2)]),
               kv=k_ref.shape[2], bs=bs, sentinel=sentinel, window=window,
               sqrt_hd=sqrt_hd)


def _gqa_q_kernel(table_ref, pos_ref, qe_ref, qo_ref, kc_ref, ks_ref, vc_ref,
                  vs_ref, oe_ref, oo_ref, m_ref, l_ref, acc_ref, *, bs: int,
                  sentinel: int, window: int | None, sqrt_hd: float):
    """Packed-operand twin: K/V arrive as e2m1 code pairs + e4m3 scale bits
    and dequantize in VMEM only for live cells, as even/odd halves that
    meet q's halves (and fill the output's) split outside the kernel."""
    def load_kv():
        def halves(c_ref, s_ref):           # (BS, KV, .) -> (KV, BS, .)
            return _dequant_halves(c_ref[0].astype(jnp.int32).transpose(1, 0, 2),
                                   s_ref[0].astype(jnp.int32).transpose(1, 0, 2))
        ks = [k.transpose(0, 2, 1) for k in halves(kc_ref, ks_ref)]
        return ks, list(halves(vc_ref, vs_ref))

    _gqa_sweep(table_ref, pos_ref, (qe_ref, qo_ref), (oe_ref, oo_ref),
               m_ref, l_ref, acc_ref, load_kv, kv=kc_ref.shape[2], bs=bs,
               sentinel=sentinel, window=window, sqrt_hd=sqrt_hd)


def _mla_sweep(table_ref, pos_ref, qa_refs, qr_refs, o_refs,
               m_ref, l_ref, acc_ref, load_cc_kc, *, bs: int, sentinel: int,
               scale: float):
    """Shared MLA flash sweep over feature parts (see `_gqa_sweep`);
    `load_cc_kc()` yields the cell's f32 latent operands per part,
    (BS, lora_i) and (BS, rope_i)."""
    b, j = pl.program_id(0), pl.program_id(1)
    sq, h = qa_refs[0].shape[1], qa_refs[0].shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p0 = pos_ref[b]
    live = (table_ref[b, j] < sentinel) & (j * bs <= p0 + sq - 1)

    @pl.when(live)
    def _block():
        ccs, kcs = load_cc_kc()
        parts = [jnp.dot(q_ref[0].astype(jnp.float32).reshape(sq * h, -1),
                         c.T, preferred_element_type=jnp.float32)
                 for q_ref, c in zip((*qa_refs, *qr_refs), (*ccs, *kcs))]
        s = functools.reduce(jnp.add, parts) * scale
        s = s.reshape(sq, h, bs)
        kj, qpos = _positions(p0, sq, bs, j)
        ok = (kj <= qpos)[:, None, :]       # (Sq, 1, BS)

        def vals(p):                        # (Sq, H, BS) -> latent readout
            pf = p.reshape(sq * h, bs)
            return [jnp.dot(pf, c, preferred_element_type=jnp.float32
                            ).reshape(sq, h, c.shape[-1]) for c in ccs]

        _online_update(s, ok, m_ref, l_ref, acc_ref, vals)

    @pl.when(j == pl.num_programs(1) - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)[..., None]
        for i, o_ref in enumerate(o_refs):
            o_ref[0] = acc_ref[i] / l


def _mla_kernel(table_ref, pos_ref, qa_ref, qr_ref, cc_ref, kc_ref, o_ref,
                m_ref, l_ref, acc_ref, *, bs: int, sentinel: int,
                scale: float):
    _mla_sweep(table_ref, pos_ref, (qa_ref,), (qr_ref,), (o_ref,),
               m_ref, l_ref, acc_ref,
               lambda: ([cc_ref[0].astype(jnp.float32)],
                        [kc_ref[0].astype(jnp.float32)]),
               bs=bs, sentinel=sentinel, scale=scale)


def _mla_q_kernel(table_ref, pos_ref, qae_ref, qao_ref, qre_ref, qro_ref,
                  ccc_ref, ccs_ref, kcc_ref, kcs_ref, oe_ref, oo_ref,
                  m_ref, l_ref, acc_ref, *, bs: int, sentinel: int,
                  scale: float):
    """Packed-operand twin: both latent pools arrive as NVFP4 bytes and
    dequantize to even/odd halves (see `_gqa_q_kernel`)."""
    _mla_sweep(table_ref, pos_ref, (qae_ref, qao_ref), (qre_ref, qro_ref),
               (oe_ref, oo_ref), m_ref, l_ref, acc_ref,
               lambda: (list(_dequant_halves(ccc_ref[0], ccs_ref[0])),
                        list(_dequant_halves(kcc_ref[0], kcs_ref[0]))),
               bs=bs, sentinel=sentinel, scale=scale)


def _table_spec_index(sentinel):
    """Index map resolving the physical pool block from the prefetched table
    (the whole point: the pipeline fetches `table[b, j]`, never a view).
    Sentinel entries clamp to the LAST pool block (sentinel - 1) — the
    cell's compute is predicated off, so the clamped fetch is a buffer
    revisit, not extra traffic."""
    def index(b, j, table_ref, pos_ref):
        return (jnp.minimum(table_ref[b, j], sentinel - 1), 0, 0, 0)
    return index


def _row(*block):
    """BlockSpec of one batch row's full (Sq, H, d) slab."""
    return pl.BlockSpec((1, *block), lambda i, j, t, p: (i, 0, 0, 0))


def _gqa_grid_spec(b, maxb, sq, h, kv, q_dims, pool_blocks, n_blocks,
                   vdp, n_parts):
    """(row, logical block) grid with the table-resolved pool BlockSpecs;
    one q input and one output per feature part of width `q_dims[i]` /
    `vdp`, and an (n_parts, KV, Sq, rep, vdp) accumulator."""
    idx = _table_spec_index(n_blocks)
    rep = h // kv
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxb),
        in_specs=[_row(sq, h, d) for d in q_dims]
                 + [pl.BlockSpec(blk, idx) for blk in pool_blocks],
        out_specs=[_row(sq, h, vdp)] * n_parts,
        scratch_shapes=[
            pltpu.VMEM((kv, sq, rep), jnp.float32),
            pltpu.VMEM((kv, sq, rep), jnp.float32),
            pltpu.VMEM((n_parts, kv, sq, rep, vdp), jnp.float32),
        ],
    )


def paged_gqa_call(q, k_pool, v_pool, table, pos, *, window: int | None,
                   interpret: bool):
    b, sq, h, hd = q.shape
    n_blocks, bs, kv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    vd = v_pool.shape[3]
    sqrt_hd = float(np.sqrt(np.float32(hd)))  # matches decode_sdpa's divisor
    grid_spec = _gqa_grid_spec(b, table.shape[1], sq, h, kv, [hd],
                               [(1, bs, kv, hd), (1, bs, kv, vd)], n_blocks,
                               vd, 1)
    (out,) = pl.pallas_call(
        functools.partial(_gqa_kernel, bs=bs, sentinel=n_blocks,
                          window=window, sqrt_hd=sqrt_hd),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, sq, h, vd), jnp.float32)],
        interpret=interpret,
    )(table, pos, q, k_pool, v_pool)
    return out


def paged_gqa_q_call(q, k_codes, k_scales, v_codes, v_scales, table, pos, *,
                     window: int | None, interpret: bool):
    """GQA flash-decode over the NVFP4-packed pool: same grid, same index
    maps, but each pool operand is a (codes, scale-bits) uint8 pair whose
    BlockSpecs move 0.28125x the bf16 bytes per cell. q enters, and the
    output leaves, as even/odd feature halves (see `_dequant_halves`)."""
    b, sq, h, hd = q.shape
    n_blocks, bs, kv = k_codes.shape[0], k_codes.shape[1], k_codes.shape[2]
    vdh = v_codes.shape[3]
    sqrt_hd = float(np.sqrt(np.float32(hd)))  # matches decode_sdpa's divisor
    grid_spec = _gqa_grid_spec(
        b, table.shape[1], sq, h, kv, [hd // 2] * 2,
        [(1, bs, kv, hd // 2), (1, bs, kv, hd // F.GROUP),
         (1, bs, kv, vdh), (1, bs, kv, 2 * vdh // F.GROUP)],
        n_blocks, vdh, 2)
    oe, oo = pl.pallas_call(
        functools.partial(_gqa_q_kernel, bs=bs, sentinel=n_blocks,
                          window=window, sqrt_hd=sqrt_hd),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, sq, h, vdh), jnp.float32)] * 2,
        interpret=interpret,
    )(table, pos, *_split_halves(q), k_codes, k_scales, v_codes, v_scales)
    return _interleave_halves(oe, oo)


def _mla_grid_spec(b, maxb, sq, h, q_dims, pool_blocks, n_blocks, lorap,
                   n_parts):
    """MLA twin of `_gqa_grid_spec` (3-D shared latent pools)."""
    idx = _table_spec_index(n_blocks)

    def pool_idx3(i, j, t, p):
        return idx(i, j, t, p)[:3]

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxb),
        in_specs=[_row(sq, h, d) for d in q_dims]
                 + [pl.BlockSpec(blk, pool_idx3) for blk in pool_blocks],
        out_specs=[_row(sq, h, lorap)] * n_parts,
        scratch_shapes=[
            pltpu.VMEM((sq, h), jnp.float32),
            pltpu.VMEM((sq, h), jnp.float32),
            pltpu.VMEM((n_parts, sq, h, lorap), jnp.float32),
        ],
    )


def paged_mla_call(q_abs, q_rope, cc_pool, kc_pool, table, pos, *,
                   scale: float, interpret: bool):
    b, sq, h, lora = q_abs.shape
    rope = q_rope.shape[3]
    n_blocks, bs = cc_pool.shape[0], cc_pool.shape[1]
    grid_spec = _mla_grid_spec(b, table.shape[1], sq, h, [lora, rope],
                               [(1, bs, lora), (1, bs, rope)], n_blocks,
                               lora, 1)
    (out,) = pl.pallas_call(
        functools.partial(_mla_kernel, bs=bs, sentinel=n_blocks, scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, sq, h, lora), jnp.float32)],
        interpret=interpret,
    )(table, pos, q_abs, q_rope, cc_pool, kc_pool)
    return out


def paged_mla_q_call(q_abs, q_rope, cc_codes, cc_scales, kc_codes, kc_scales,
                     table, pos, *, scale: float, interpret: bool):
    """Absorbed-form MLA flash-decode over NVFP4-packed latent pools (q and
    the output as even/odd feature halves, as in `paged_gqa_q_call`)."""
    b, sq, h, lora = q_abs.shape
    rope = q_rope.shape[3]
    n_blocks, bs = cc_codes.shape[0], cc_codes.shape[1]
    grid_spec = _mla_grid_spec(
        b, table.shape[1], sq, h, [lora // 2] * 2 + [rope // 2] * 2,
        [(1, bs, lora // 2), (1, bs, lora // F.GROUP),
         (1, bs, rope // 2), (1, bs, rope // F.GROUP)],
        n_blocks, lora // 2, 2)
    oe, oo = pl.pallas_call(
        functools.partial(_mla_q_kernel, bs=bs, sentinel=n_blocks,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, sq, h, lora // 2),
                                        jnp.float32)] * 2,
        interpret=interpret,
    )(table, pos, *_split_halves(q_abs), *_split_halves(q_rope),
      cc_codes, cc_scales, kc_codes, kc_scales)
    return _interleave_halves(oe, oo)
