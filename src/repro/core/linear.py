"""QuartetLinear: the fully-NVFP4 linear-layer computation graph (paper Fig. 3)
as a jax.custom_vjp, parameterized by a Scheme.

Simulated-NVFP4 GEMM semantics (TPU adaptation, see DESIGN.md Section 2):
the MXU consumes bf16 "block values" (fp4_code * e4m3_scale, exactly
representable in bf16 because 2 + 4 significant bits < 8), accumulates in
fp32, and the two per-tensor FP32 scales multiply the GEMM output — precisely
what a Blackwell NVFP4 tensor core computes, so results are bit-faithful to
hardware NVFP4 up to fp32 accumulation order.

Backward orientation (inner dims):
    Y  = X  @ W^T    inner K   (forward quantizers, groups along K)
    dX = E  @ W      inner N   (E rows and W^T rows quantized along N)
    dW = E^T @ X     inner M   (E^T and X^T quantized along M = batch*seq)

Activations are saved for the backward pass as *packed NVFP4* (uint8 nibble
pairs + e4m3 scales = 4.5 bits/element) whenever the forward quantizes them —
the memory-roofline lever on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import formats as F
from repro.core import ms_eden as ME
from repro.core import quant as Q
from repro.core import rht as R
from repro.core import schemes as S
from repro.core import scopes as SC


# --------------------------------------------------------------------------
# sharding hints (set by launch/dryrun before lowering; None on single-host)
#
# Perf iteration 1 (EXPERIMENTS.md §Perf): without these, GSPMD loses the
# token-dim sharding at the RHT block reshape whenever the inner-dim shard is
# not a multiple of 128 (e.g. d_ff=11008 over 16 devices = 688), and falls
# back to REPLICATING the (tokens x d) gradient operands on every device —
# ~5x redundant compute and memory traffic. Constraining rows(tokens)->DP,
# weight-rows->TP and keeping the quantization/rotation axis local fixes the
# partitioning for every backward GEMM.
# --------------------------------------------------------------------------

# {"dp": ("pod","data") | ("data",), "tp": "model", "dp_size": int, "tp_size": int}
MESH_AXES: dict | None = None

import contextlib


@contextlib.contextmanager
def no_hints():
    """Trace-time hint suppression: vmapped per-expert GEMMs already live in
    the EP-optimal (E->model, capacity, d) layout; the token-level hints
    would force a reshard of every dispatch buffer (measured 18x collective
    blow-up on deepseek-v3 — Perf iteration 6)."""
    global MESH_AXES
    old = MESH_AXES
    MESH_AXES = None
    try:
        yield
    finally:
        MESH_AXES = old


def _hint(x: jax.Array, spec: tuple) -> jax.Array:
    if MESH_AXES is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


def _dp(dim: int):
    """DP axes if the dim divides evenly, else None (replicate)."""
    if MESH_AXES is None or dim % max(MESH_AXES["dp_size"], 1):
        return None
    return MESH_AXES["dp"]


def _tp(dim: int):
    if MESH_AXES is None or dim % max(MESH_AXES["tp_size"], 1):
        return None
    return MESH_AXES["tp"]


def _tp_inner(dim: int, block: int):
    """TP for a quantization/rotation axis only if every shard holds whole
    blocks (RHT 128-blocks / scale 16-groups stay device-local). Perf
    iteration 3: keeps E (tokens x N) model-sharded through the dX GEMM
    instead of all-gathering it every layer."""
    if MESH_AXES is None or dim % (max(MESH_AXES["tp_size"], 1) * block):
        return None
    return MESH_AXES["tp"]


UNC = jax.sharding.PartitionSpec.UNCONSTRAINED


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _key(seed: jax.Array, tag: int) -> jax.Array:
    """Derive a typed PRNG key from a uint32[2] seed and an integer tag."""
    k = jax.random.wrap_key_data(seed.astype(jnp.uint32))
    return jax.random.fold_in(k, tag)


def _block_values(qt: Q.QTensor) -> jax.Array:
    """fp4 * e4m3 block values in bf16 (lossless), without the fp32 gscale."""
    s = jnp.repeat(qt.scales, F.GROUP, axis=-1)
    return (qt.vals * s).astype(jnp.bfloat16)


def _qmm(qa: Q.QTensor, qb: Q.QTensor) -> jax.Array:
    """Simulated NVFP4 GEMM: (Ma, D) x (Mb, D) -> (Ma, Mb) in fp32."""
    with jax.named_scope(SC.GEMM):
        a = _block_values(qa)
        b = _block_values(qb)
        out = jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        return out * (qa.gscale * qb.gscale)


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """bf16 GEMM (Ma, D) x (Mb, D) -> (Ma, Mb), fp32 accumulation."""
    with jax.named_scope(SC.GEMM):
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _fwd_quant(x: jax.Array, kind: str) -> Q.QTensor:
    with jax.named_scope(SC.QUANT_FWD):
        if kind == "rtn":
            return Q.quant_rtn(x, s=Q.S_EDEN)
        if kind == "fos":
            return Q.quant_four_over_six(x)
        if kind == "square":
            return Q.quant_square_block(x)
    raise ValueError(f"unknown forward quantizer {kind}")


def quant_sr_fos(x: jax.Array, key: jax.Array) -> Q.QTensor:
    """FourOverSix backward quantizer: deterministic min-MSE branch choice
    (between the absmax->s* and absmax->s**4/6 clipping grids, same
    placements as the RTN 4/6 — reproduces the paper's 17.5e-3 Table-1 row)
    followed by SR. Both the branch choice AND the SR-through-clipping
    introduce bias (paper Sec. 4.2, App. A Fig. 9)."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf))
    gscale = absmax / ((Q.S_EDEN * 4.0 / 6.0) * F.FP8_MAX)
    gscale = jnp.where(gscale == 0, 1.0, gscale)
    gmax = Q._group_absmax(xf)

    def branch(div):
        scales = F.fp8_rtn(gmax / (gscale * div))
        denom = jnp.repeat(scales, F.GROUP, axis=-1) * gscale
        xs = Q._safe_div(xf, denom)
        deq_rtn = F.fp4_rtn(xs) * denom
        g = (deq_rtn - xf).reshape(*xf.shape[:-1], xf.shape[-1] // F.GROUP, F.GROUP)
        return scales, xs, jnp.sum(g * g, axis=-1)

    s6, xs6, m6 = branch(Q.S_EDEN)
    s4, xs4, m4 = branch(Q.S_EDEN * 4.0 / 6.0)
    use4 = m4 < m6
    scales = jnp.where(use4, s4, s6)
    xs = jnp.where(jnp.repeat(use4, F.GROUP, axis=-1), xs4, xs6)
    q = F.fp4_sr(xs, key)
    return Q.QTensor(q, scales, gscale)


def _pad_rows_to(x: jax.Array, mult: int) -> jax.Array:
    """Zero-pad the last axis to a multiple of `mult` (safe for GEMM sums)."""
    d = x.shape[-1]
    pad = (-d) % mult
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _bwd_gemm(
    a: jax.Array,            # (Ma, D)
    b: jax.Array,            # (Mb, D)
    bwd: str,                # sr | sr_fos | ms_eden
    quant_a: bool,
    quant_b: bool,
    use_rht: bool,
    seed: jax.Array,
    tag: int,
    specs: tuple | None = None,  # ((rows_a, cols_a), (rows_b, cols_b)) hints
) -> jax.Array:
    """One backward GEMM a @ b^T with per-scheme quantization on inner dim D."""
    if not (quant_a or quant_b):
        return _mm(a, b)

    d = a.shape[-1]
    mult = 128 if (d % 128) else 16  # pad target for grouping/rotation
    a = _pad_rows_to(a, 16 if not use_rht else mult)
    b = _pad_rows_to(b, 16 if not use_rht else mult)
    # fp32 BEFORE the hints: counterintuitively measured better — bf16-domain
    # hints made GSPMD re-gather post-cast (iter 4/5 refuted, +75% wire);
    # fp32-domain constraints keep one gather per operand (iter 2, best).
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if specs is not None:
        a = _hint(a, specs[0])
        b = _hint(b, specs[1])

    k_rht = _key(seed, tag)
    k_a = _key(seed, tag + 1)
    k_b = _key(seed, tag + 2)

    if bwd == "ms_eden":
        assert quant_a and quant_b and use_rht, "MS-EDEN requires re-quantizing both operands"
        with jax.named_scope(SC.QUANT_BWD):
            qa = ME.ms_eden(a, k_rht, k_a).qt
            qb = ME.ms_eden(b, k_rht, k_b).qt
        return _qmm(qa, qb)  # rotations cancel along D

    quantizer = Q.quant_sr if bwd == "sr" else quant_sr_fos
    with jax.named_scope(SC.QUANT_BWD):
        ar = R.rht(a, k_rht) if (use_rht and quant_a and quant_b) else a
        br = R.rht(b, k_rht) if (use_rht and quant_a and quant_b) else b
        if quant_a and quant_b:
            qa, qb = quantizer(ar, k_a), quantizer(br, k_b)
        elif quant_a:
            ar = Q.dequant(quantizer(ar, k_a), jnp.bfloat16)
        else:
            br = Q.dequant(quantizer(br, k_b), jnp.bfloat16)
    if quant_a and quant_b:
        return _qmm(qa, qb)
    return _mm(ar, br)


# --------------------------------------------------------------------------
# packed NVFP4 residuals (activation memory: 4.5 bits/element)
# --------------------------------------------------------------------------

def _pack_qt(qt: Q.QTensor):
    packed = F.pack_fp4(qt.codes)
    scales8 = jnp.clip(qt.scales, 0, F.FP8_MAX).astype(jnp.float8_e4m3fn)
    return packed, scales8, qt.gscale


def _unpack_qt(res) -> Q.QTensor:
    packed, scales8, gscale = res
    return Q.QTensor(F.fp4_decode(F.unpack_fp4(packed)),
                     scales8.astype(jnp.float32), gscale)


# --------------------------------------------------------------------------
# quantize-once weights (serving): the deterministic forward quantizers make
# W's NVFP4 image a pure function of W, so inference packs it ONCE and decode
# never re-runs weight quantization (serve/prequant.py builds these).
# --------------------------------------------------------------------------

import typing


class PackedQWeight(typing.NamedTuple):
    """An offline-packed NVFP4 weight: 4.5 bits/element at rest.

    Bit-exact round trip: `packed` holds E2M1 codes (2/byte), `scales8` the
    e4m3 group scales (both produced by the same `_fwd_quant` the per-step
    path runs), so unpacking reproduces the per-step QTensor exactly.
    A NamedTuple => a pytree: stacked-layer stacks scan/vmap transparently.
    """

    packed: jax.Array   # uint8 (..., N, K // 2)
    scales8: jax.Array  # float8_e4m3fn (..., N, K // 16)
    gscale: jax.Array   # float32 (...,) per-tensor scale

    @property
    def out_features(self) -> int:
        return self.packed.shape[-2]


def pack_weight(w: jax.Array, kind: str) -> PackedQWeight:
    """Quantize one 2D weight with forward quantizer `kind` and pack it."""
    packed, scales8, gscale = _pack_qt(_fwd_quant(w, kind))
    return PackedQWeight(packed, scales8, gscale)


def _qlinear_packed(x: jax.Array, w: PackedQWeight, scheme: str) -> jax.Array:
    """Inference forward against a prequantized weight.

    Bit-identical to `_qlinear_fwd` on the raw weight: the activation side
    still quantizes per call (activations change every step; weights don't).
    """
    sch = S.get(scheme)
    assert sch.fwd_w != "none", \
        f"scheme {scheme} does not quantize weights; pass the raw array"
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    qw = _unpack_qt((w.packed, w.scales8, w.gscale))
    if sch.fwd_x != "none":
        y = _qmm(_fwd_quant(xf, sch.fwd_x), qw)
    else:
        y = _mm(xf, Q.dequant(qw, jnp.bfloat16))
    return y.astype(x.dtype).reshape(*lead, -1)


# --------------------------------------------------------------------------
# the custom-vjp linear
# --------------------------------------------------------------------------

def qlinear(x: jax.Array, w, seed: jax.Array, scheme: str = "quartet2"):
    """y = x @ w^T under the given quantization scheme.

    x: (..., K) activations; w: (N, K) weight — raw array (training) or
    PackedQWeight (quantize-once serving); seed: uint32[2] per-step/site
    randomness (ignored by deterministic schemes).
    """
    if isinstance(w, PackedQWeight):
        return _qlinear_packed(x, w, scheme)
    return _qlinear_cvjp(x, w, seed, scheme)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qlinear_cvjp(x: jax.Array, w: jax.Array, seed: jax.Array,
                  scheme: str = "quartet2"):
    y, _ = _qlinear_fwd(x, w, seed, scheme)
    return y


def _qlinear_fwd(x, w, seed, scheme):
    sch = S.get(scheme)
    lead = x.shape[:-1]
    k = x.shape[-1]
    xf = x.reshape(-1, k)

    if not sch.is_quantized:
        y = _mm(xf, w).astype(x.dtype)
        return y.reshape(*lead, -1), (x, w, seed)

    # Megatron-style fwd layout: tokens -> DP, weight out-dim -> TP, the
    # quantization axis K local. (Perf iter 3 tried UNCONSTRAINED K — refuted:
    # +75% all-gather wire; the explicit column layout measures best.)
    xf = _hint(xf, (_dp(xf.shape[0]), None))
    w = _hint(w, (_tp(w.shape[0]), None))
    qx = _fwd_quant(xf, sch.fwd_x) if sch.fwd_x != "none" else None
    qw = _fwd_quant(w, sch.fwd_w) if sch.fwd_w != "none" else None

    if qx is not None and qw is not None:
        y = _qmm(qx, qw)
    elif qx is not None:
        y = _mm(Q.dequant(qx, jnp.bfloat16), w)
    elif qw is not None:
        y = _mm(xf, Q.dequant(qw, jnp.bfloat16))
    else:
        y = _mm(xf, w)
    y = y.astype(x.dtype).reshape(*lead, -1)

    # Save activations as packed NVFP4 when the forward quantized them
    # (paper Sec. 5: backward re-quantizes the SAVED quantized activations).
    x_res = _pack_qt(qx) if qx is not None else x
    return y, (x_res, w, seed)


def _qlinear_bwd(scheme, res, e):
    sch = S.get(scheme)
    x_res, w, seed = res
    n, k = w.shape
    lead = e.shape[:-1]
    ef = e.reshape(-1, n)  # stays bf16 until after sharding hints
    m = ef.shape[0]

    if isinstance(x_res, tuple):
        xf = Q.dequant(_unpack_qt(x_res))          # (M, K) fp32, NVFP4-exact
    else:
        xf = x_res.reshape(-1, k).astype(jnp.float32)

    if not sch.is_quantized or sch.bwd == "none":
        dx = _mm(ef, w.T)                          # (M, K)
        dw = _mm(ef.T, xf.T)                       # (N, K)
    else:
        m_pad = m + ((-m) % 128)
        # dX operands: tokens -> DP, W^T rows (K) -> TP, inner dim N local.
        # (Perf iter 3 tried keeping N TP-sharded — refuted: the row-parallel
        # dX partial-sum all-reduces cost 2x more wire than the bf16 E
        # gather; see EXPERIMENTS.md §Perf.)
        dx_specs = ((_dp(m_pad), None), (_tp(k), None))
        # dW operands: E^T rows = N -> TP; X^T rows follow; inner dim M
        # (tokens) stays DP-sharded — XLA reduces partial dW with a single
        # all-reduce, and 128-token RHT blocks stay shard-local.
        # X^T rows pinned replicated (UNC let GSPMD model-gather X — refuted
        # in Perf iter 4; explicit None keeps X purely DP-sharded on tokens)
        dw_specs = ((_tp(n), _dp(m_pad)), (None, _dp(m_pad)))

        # ---- dX = E @ W (inner dim N) ----
        if sch.quant_dx_e:
            if sch.dx_w_mode == "requant":
                # de-quantize saved W, re-quantize along N with shared RHT
                w_saved = (Q.dequant(_fwd_quant(w, sch.fwd_w))
                           if sch.fwd_w != "none" else w.astype(jnp.float32))
                dx = _bwd_gemm(ef, w_saved.T, sch.bwd, True, True,
                               use_rht=True, seed=seed, tag=1, specs=dx_specs)
            elif sch.dx_w_mode == "reuse":
                assert sch.fwd_w == "square", "scale reuse needs square blocks"
                wq = Q.dequant(_fwd_quant(w, "square"), jnp.bfloat16)
                dx = _bwd_gemm(ef, wq.T, sch.bwd, True, False,
                               use_rht=False, seed=seed, tag=1, specs=dx_specs)
            else:  # "bf16"
                dx = _bwd_gemm(ef, w.T.astype(jnp.float32), sch.bwd, True, False,
                               use_rht=False, seed=seed, tag=1, specs=dx_specs)
        else:
            dx = _mm(ef, w.T)

        # ---- dW = E^T @ X (inner dim M) ----
        if sch.quant_dw_e or sch.quant_dw_x:
            dw = _bwd_gemm(ef.T, xf.T, sch.bwd, sch.quant_dw_e, sch.quant_dw_x,
                           use_rht=sch.rht_dw, seed=seed, tag=4, specs=dw_specs)
        else:
            dw = _mm(ef.T, xf.T)

    dx = dx.reshape(*lead, k).astype(e.dtype)
    dw = dw.astype(w.dtype)
    return dx, dw, None


_qlinear_cvjp.defvjp(_qlinear_fwd, _qlinear_bwd)


def dense(x: jax.Array, w: jax.Array) -> jax.Array:
    """Plain bf16 linear (router / frontends / optionally LM head)."""
    out = jax.lax.dot_general(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        (((x.ndim - 1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return out.astype(x.dtype)
