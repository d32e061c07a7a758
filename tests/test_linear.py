"""QuartetLinear behaviour: gradient quality ordering, unbiasedness (Fig. 9),
scheme plumbing, packed residuals."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant as Q
from repro.core import schemes as S
from repro.core.linear import (_bwd_gemm, _fwd_quant, _pack_qt, _qlinear_fwd,
                               _unpack_qt, qlinear)

SEED = jnp.array([3, 7], jnp.uint32)


@pytest.fixture(scope="module")
def xw():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 256), jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(1), (384, 256)) / 16.0).astype(jnp.bfloat16)
    return x, w


def grads(x, w, scheme, seed=SEED):
    def loss(x, w):
        return jnp.sum(qlinear(x, w, seed, scheme).astype(jnp.float32) ** 2)
    return jax.grad(loss, (0, 1))(x, w)


ALL_SCHEMES = S.names()
W_IMAGE_SCHEMES = ["quartet2", "four_over_six", "tetrajet_v2", "nvidia"]


@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_all_schemes_run_and_finite(xw, scheme):
    x, w = xw
    y = qlinear(x, w, SEED, scheme)
    assert y.shape == (2, 64, 384) and y.dtype == x.dtype
    assert not bool(jnp.isnan(y.astype(jnp.float32)).any())
    dx, dw = grads(x, w, scheme)
    assert dx.shape == x.shape and dw.shape == w.shape
    assert not bool(jnp.isnan(dx.astype(jnp.float32)).any())
    assert not bool(jnp.isnan(dw.astype(jnp.float32)).any())


def test_bf16_scheme_is_exact_linear(xw):
    x, w = xw
    y = qlinear(x, w, SEED, "bf16")
    ref = jax.lax.dot_general(x, w, (((2,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    assert np.allclose(np.asarray(y, np.float32), np.asarray(ref), rtol=1e-2)


def test_forward_quant_error_ordering(xw):
    """4/6 < plain RTN < square-block forward error (paper Fig. 2 / Table 1)."""
    x, w = xw
    ref = np.asarray(qlinear(x, w, SEED, "bf16"), np.float32)

    def err(scheme):
        y = np.asarray(qlinear(x, w, SEED, scheme), np.float32)
        return np.linalg.norm(y - ref) / np.linalg.norm(ref)

    e_fos, e_rtn, e_sq = err("fwd_rtn_1x16_fos"), err("fwd_rtn_1x16"), err("fwd_square")
    assert e_fos < e_rtn < e_sq, (e_fos, e_rtn, e_sq)


def test_quartet2_beats_sr_baselines(xw):
    """Gradient error: quartet2 < tetrajet_v2 / nvidia (paper Fig. 4)."""
    x, w = xw
    rdx, rdw = grads(x, w, "bf16")

    def err(scheme, n=8):
        tot = 0.0
        for i in range(n):
            dx, dw = grads(x, w, scheme, jnp.array([11, i], jnp.uint32))
            tot += float(jnp.linalg.norm((dw - rdw).astype(jnp.float32)))
        return tot / n

    q2, tj, nv = err("quartet2"), err("tetrajet_v2"), err("nvidia")
    assert q2 < tj and q2 < nv, (q2, tj, nv)


def test_mseden_requant_beats_sr_norequant(xw):
    """Fig. 1 (e) vs (d): the paper's argument for dropping square blocks."""
    x, w = xw
    rdx, _ = grads(x, w, "bf16")

    def err(scheme, n=8):
        tot = 0.0
        for i in range(n):
            dx, _ = grads(x, w, scheme, jnp.array([13, i], jnp.uint32))
            tot += float(jnp.linalg.norm((dx - rdx).astype(jnp.float32)))
        return tot / n

    assert err("abl_e_ms_eden") < err("abl_d_sr")


def test_backward_unbiasedness_concentration():
    """Fig. 9: averaged quantized grad -> exact grad at rate ~1/B for the
    unbiased schemes; MS-EDEN has the lowest variance."""
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 256), jnp.float32)
    w = (jax.random.normal(jax.random.PRNGKey(1), (256, 256)) / 16).astype(jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(2), (128, 256), jnp.float32)

    def gradw(seed, scheme):
        return jax.grad(lambda w: jnp.sum(qlinear(x, w, seed, scheme) * ct))(w)

    ref = gradw(jnp.array([0, 0], jnp.uint32), "bf16")

    def errs(scheme, batches=(8, 128)):
        f = jax.jit(jax.vmap(lambda s: gradw(s, scheme)))
        out = []
        for b in batches:
            seeds = jnp.stack([jnp.full((b,), 17, jnp.uint32),
                               jnp.arange(b, dtype=jnp.uint32)], -1)
            g = jnp.mean(f(seeds), 0)
            out.append(float(jnp.sum((g - ref) ** 2) / jnp.sum(ref ** 2)))
        return out

    e_eden = errs("abl_e_ms_eden")
    e_sr = errs("abl_e_sr")
    # 16x more samples -> ~16x lower error (allow slack for MC noise)
    assert e_eden[0] / e_eden[1] > 8, e_eden
    assert e_sr[0] / e_sr[1] > 8, e_sr
    # MS-EDEN variance < SR variance (paper's central claim)
    assert e_eden[0] < e_sr[0]


def test_padding_non_multiple_of_128_tokens():
    """dW inner dim M=batch*seq gets zero-padded; grads stay correct-shaped."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16, 64), jnp.bfloat16)  # M=48
    w = (jax.random.normal(jax.random.PRNGKey(1), (128, 64)) / 8).astype(jnp.bfloat16)
    dx, dw = grads(x, w, "quartet2")
    assert dx.shape == x.shape and dw.shape == w.shape
    assert not bool(jnp.isnan(dw.astype(jnp.float32)).any())


def test_determinism_given_seed(xw):
    x, w = xw
    a = grads(x, w, "quartet2", jnp.array([5, 5], jnp.uint32))
    b = grads(x, w, "quartet2", jnp.array([5, 5], jnp.uint32))
    assert np.array_equal(np.asarray(a[1], np.float32), np.asarray(b[1], np.float32))
    c = grads(x, w, "quartet2", jnp.array([5, 6], jnp.uint32))
    assert not np.array_equal(np.asarray(a[1], np.float32), np.asarray(c[1], np.float32))


def _f32(a):
    return np.asarray(jnp.asarray(a, jnp.float32))


@pytest.mark.parametrize("scheme", W_IMAGE_SCHEMES)
def test_backward_reads_the_forward_w_image(xw, scheme):
    """The forward saves W's NVFP4 image and the backward reads it: dX and dW
    are bit-equal to a backward that quantizes W again from the raw weight."""
    x, w = xw
    sch = S.get(scheme)
    assert sch.saves_w_image
    ct = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 384), jnp.bfloat16)

    def loss(x, w):
        y = qlinear(x, w, SEED, scheme).astype(jnp.float32)
        return jnp.sum(y * ct.astype(jnp.float32))   # y's cotangent is ct

    dx, dw = jax.grad(loss, (0, 1))(x, w)

    k = x.shape[-1]
    ef = ct.reshape(-1, ct.shape[-1])
    qw = _fwd_quant(w, sch.fwd_w)
    if sch.dx_w_mode == "requant":
        rdx = _bwd_gemm(ef, Q.dequant(qw).T, sch.bwd, True, True,
                        use_rht=True, seed=SEED, tag=1)
    else:  # "reuse": the square-block image as it is, in bf16
        rdx = _bwd_gemm(ef, Q.dequant(qw, jnp.bfloat16).T, sch.bwd, True, False,
                        use_rht=False, seed=SEED, tag=1)
    xq = Q.dequant(_unpack_qt(_pack_qt(_fwd_quant(x.reshape(-1, k), sch.fwd_x))))
    rdw = _bwd_gemm(ef.T, xq.T, sch.bwd, sch.quant_dw_e, sch.quant_dw_x,
                    use_rht=sch.rht_dw, seed=SEED, tag=4)
    assert dx.dtype == x.dtype and dw.dtype == w.dtype
    np.testing.assert_array_equal(_f32(dx), _f32(rdx.reshape(x.shape).astype(x.dtype)))
    np.testing.assert_array_equal(_f32(dw), _f32(rdw.astype(w.dtype)))

    _, (_, image, _) = _qlinear_fwd(x, w, SEED, scheme)
    assert image.shape == w.shape and image.dtype == w.dtype
    np.testing.assert_array_equal(_f32(image.dequant()), _f32(Q.dequant(qw)))


@pytest.mark.parametrize(
    "scheme", [n for n in ALL_SCHEMES if n not in W_IMAGE_SCHEMES])
def test_other_schemes_save_the_raw_weight(xw, scheme):
    """Schemes whose dX GEMM reads W in bf16, or whose backward is bf16,
    keep W itself as the residual."""
    x, w = xw
    assert not S.get(scheme).saves_w_image
    _, (_, w_res, _) = _qlinear_fwd(x, w, SEED, scheme)
    assert w_res is w
