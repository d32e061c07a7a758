"""Public jit'd entry points for the Pallas kernels (the `ops.py` layer of
the kernel contract: <name>.py kernel + ops.py wrapper + ref.py oracle).

On real TPU hardware pass interpret=False; this container validates in
interpret mode (the kernel bodies execute in Python on CPU). The paged
decode kernels resolve `interpret=None` from the active backend so the
serving engine can call them unconditionally.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import paged_attention as PA
from repro.kernels.fp4_matmul import fp4_matmul
from repro.kernels.ms_eden_requant import ms_eden_requant
from repro.kernels.nvfp4_quant import nvfp4_fos_quant

__all__ = ["nvfp4_fos_quant", "ms_eden_requant", "fp4_matmul",
           "quartet2_backward_gemm", "paged_attention",
           "paged_mla_attention", "paged_attention_q",
           "paged_mla_attention_q"]


def _resolve_interpret(interpret: bool | None) -> bool:
    """Kernels compile only on TPU; anywhere else (CPU CI, the dry-run
    host mesh) they run in interpret mode."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _unpartitioned(call, *args):
    """Run a Pallas call where GSPMD cannot partition it.

    Mosaic kernels cannot be partitioned automatically. The mesh-sharded
    serving step is manual over "data" only and leaves "model" to GSPMD,
    so there the call is made manual over the remaining non-trivial axes
    with every operand replicated: each device of a "model" group runs the
    whole call on its own shard of slots. Off-mesh (or when no such axis
    is larger than 1) the call is made as it is."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = set() if mesh.empty else {
        a for a in mesh.auto_axes if mesh.shape[a] > 1}
    if not auto:
        return call(*args)
    P = jax.sharding.PartitionSpec
    return jax.shard_map(call, mesh=mesh, in_specs=P(), out_specs=P(),
                         axis_names=auto, check_vma=False)(*args)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention(q, k_pool, v_pool, table, pos, *, window: int | None = None,
                    interpret: bool | None = None):
    """Flash-decode GQA attention straight off the paged KV pool.

    q: (B, Sq, H, hd); k_pool: (P, BS, KV, hd); v_pool: (P, BS, KV, vd);
    table: (B, MAXB) int32 block table (OOB sentinel == P for unallocated
    entries); pos: (B,) absolute position of each row's first query token.
    Equivalent to `decode_sdpa(q, gather_view(k_pool, table),
    gather_view(v_pool, table), pos, window=window)` without ever
    materializing the gathered views. Returns (B, Sq, H, vd) in q.dtype.
    """
    out = _unpartitioned(
        functools.partial(PA.paged_gqa_call, window=window,
                          interpret=_resolve_interpret(interpret)),
        q, k_pool, v_pool, table, jnp.asarray(pos, jnp.int32))
    return out.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("qk_dim", "interpret"))
def paged_mla_attention(q_abs, q_rope, cc_pool, kc_pool, table, pos, *,
                        qk_dim: int, interpret: bool | None = None):
    """Absorbed-form MLA flash-decode over the shared latent pools.

    q_abs: (B, Sq, H, lora) — q_nope already absorbed through W_uk;
    q_rope: (B, Sq, H, rope); cc_pool: (P, BS, lora); kc_pool: (P, BS,
    rope). Scores are (q_abs·cc + q_rope·kc) / sqrt(qk_dim) and the value
    readout is over cc itself, so the fp32 result is o_lat (B, Sq, H, lora)
    for the caller's W_uv absorption (vd != hd: the whole point of MLA).
    """
    # the f32 image of mla_decode's 1/sqrt(nope+rope), so kernel and
    # reference multiply by the identical scalar
    scale = float(np.float32(1.0) / np.sqrt(np.float32(qk_dim)))
    return _unpartitioned(
        functools.partial(PA.paged_mla_call, scale=scale,
                          interpret=_resolve_interpret(interpret)),
        q_abs, q_rope, cc_pool, kc_pool, table, jnp.asarray(pos, jnp.int32))


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_q(q, k_codes, k_scales, v_codes, v_scales, table, pos, *,
                      window: int | None = None,
                      interpret: bool | None = None):
    """Flash-decode GQA attention off the NVFP4-QUANTIZED paged pool.

    Packed-operand twin of `paged_attention`: K/V arrive as the quantized
    pool's raw leaves — e2m1 code pairs (P, BS, KV, hd/2) uint8 + e4m3
    scale bits (P, BS, KV, hd/16) uint8 per operand (the fields of
    serve.kv_pool.PackedKV, passed unbundled so this layer never imports
    serve) — and dequantize block-wise in VMEM inside the online-softmax
    sweep. Equivalent to `paged_attention` over the dequantized pools;
    the dequant is exact in f32/bf16, so parity with the gather-then-
    decode reference is the same contract as the bf16 kernel's.
    """
    out = _unpartitioned(
        functools.partial(PA.paged_gqa_q_call, window=window,
                          interpret=_resolve_interpret(interpret)),
        q, k_codes, k_scales, v_codes, v_scales, table,
        jnp.asarray(pos, jnp.int32))
    return out.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("qk_dim", "interpret"))
def paged_mla_attention_q(q_abs, q_rope, cc_codes, cc_scales, kc_codes,
                          kc_scales, table, pos, *, qk_dim: int,
                          interpret: bool | None = None):
    """Absorbed-form MLA flash-decode over NVFP4-QUANTIZED latent pools
    (packed-operand twin of `paged_mla_attention`; operands are the
    unbundled PackedKV leaves of the cc / kc pools)."""
    scale = float(np.float32(1.0) / np.sqrt(np.float32(qk_dim)))
    return _unpartitioned(
        functools.partial(PA.paged_mla_q_call, scale=scale,
                          interpret=_resolve_interpret(interpret)),
        q_abs, q_rope, cc_codes, cc_scales, kc_codes, kc_scales, table,
        jnp.asarray(pos, jnp.int32))


def quartet2_backward_gemm(a, b, rht_key, sr_key_a, sr_key_b, *,
                           interpret: bool = True):
    """Fused kernel-path backward GEMM a @ b^T with MS-EDEN re-quantization
    of both operands (rotations share `rht_key` and cancel in the product) —
    the kernel-level composition of paper Fig. 3's backward box:

        requant(a), requant(b)  ->  packed codes + scales  ->  fp4_matmul
    """
    ac, ascale, ag = ms_eden_requant(a, rht_key, sr_key_a, interpret=interpret)
    bc, bscale, bg = ms_eden_requant(b, rht_key, sr_key_b, interpret=interpret)
    from repro.core.formats import pack_fp4
    return fp4_matmul(pack_fp4(ac), ascale, pack_fp4(bc), bscale, ag, bg,
                      interpret=interpret)
