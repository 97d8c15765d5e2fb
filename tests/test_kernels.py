"""Per-Pallas-kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret mode on CPU; same pallas_calls compile natively on TPU)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hyp import given, settings, st

from repro.kernels import (ref, gemm, spmm, sddmm, rmsnorm, flash_attention,
                           decode_attention)
from repro.kernels.agg_combine import agg_combine, agg_combine_partial

RNG = np.random.default_rng(0)


def _r(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (100, 130, 70), (128, 128, 128),
                                   (257, 64, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_sweep(m, k, n, dtype):
    a, b = _r(m, k, dtype=dtype), _r(k, n, dtype=dtype)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(gemm(a, b), np.float32),
        np.asarray(ref.gemm_ref(a, b), np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("n,f,d,kk", [(50, 32, 10, 4), (300, 96, 64, 7),
                                      (128, 256, 128, 16)])
@pytest.mark.parametrize("mode", ["mean", "sum"])
def test_spmm_sweep(n, f, d, kk, mode):
    h = _r(n, f)
    nbr = jnp.asarray(RNG.integers(0, n, (d, kk)), jnp.int32)
    mask = jnp.asarray(RNG.integers(0, 2, (d, kk)), jnp.float32)
    np.testing.assert_allclose(spmm(h, nbr, mask, mode=mode),
                               ref.spmm_ref(h, nbr, mask, mode=mode),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,f,d,kk", [(60, 32, 20, 3), (130, 64, 40, 8)])
def test_sddmm_sweep(n, f, d, kk):
    h = _r(n, f)
    nbr = jnp.asarray(RNG.integers(0, n, (d, kk)), jnp.int32)
    mask = jnp.asarray(RNG.integers(0, 2, (d, kk)), jnp.float32)
    np.testing.assert_allclose(sddmm(h, nbr, mask),
                               ref.sddmm_ref(h, nbr, mask),
                               rtol=1e-5, atol=1e-5)


def _gather_case(kernel, h, nbr, mask, w, b):
    """(kernel output, ref.py oracle) for one row-gather kernel."""
    mean = ref.spmm_ref(h, nbr, mask, mode="mean")
    return {
        "spmm_mean": (lambda: spmm(h, nbr, mask, mode="mean"), lambda: mean),
        "spmm_sum": (lambda: spmm(h, nbr, mask, mode="sum"),
                     lambda: ref.spmm_ref(h, nbr, mask, mode="sum")),
        "sddmm": (lambda: sddmm(h, nbr, mask),
                  lambda: ref.sddmm_ref(h, nbr, mask)),
        "agg_combine": (lambda: agg_combine(h, nbr, mask, w, b),
                        lambda: jnp.maximum(ref.gemm_ref(mean, w) + b, 0.0)),
        "agg_combine_partial": (lambda: agg_combine_partial(h, nbr, mask, w),
                                lambda: ref.gemm_ref(mean, w)),
    }[kernel]


# (20000, 420): a padded table of 41 MB, beyond the 16 MiB scoped VMEM, so
# the rows must come from HBM; (600, 4000): a width that caps the rows per
# grid step below the default block
@pytest.mark.parametrize("n,f,d,kk", [(20000, 420, 300, 10),
                                      (600, 4000, 100, 10)])
@pytest.mark.parametrize("kernel", ["spmm_mean", "spmm_sum", "sddmm",
                                    "agg_combine", "agg_combine_partial"])
def test_row_gather_kernels(kernel, n, f, d, kk):
    """The shared HBM row gather at a non-multiple-of-128 F and large N."""
    h = _r(n, f)
    nbr = RNG.integers(0, n, (d, kk))
    nbr[0, 0], nbr[-1, -1] = n - 1, 0            # both ends of the table
    nbr = jnp.asarray(nbr, jnp.int32)
    mask = jnp.asarray(RNG.integers(0, 2, (d, kk)), jnp.float32)
    w, b = _r(f, 64) * 0.05, _r(64)
    got, want = _gather_case(kernel, h, nbr, mask, w, b)
    np.testing.assert_allclose(got(), want(), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,f", [(3, 64), (17, 256), (64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(b, f, dtype):
    x, w = _r(b, f, dtype=dtype), _r(f)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(rmsnorm(x, w.astype(dtype)), np.float32),
        np.asarray(ref.rmsnorm_ref(x, w.astype(dtype)), np.float32),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("b,hq,hkv,t,d", [(1, 2, 2, 64, 32), (2, 4, 2, 100, 64),
                                          (1, 8, 1, 33, 16)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, hq, hkv, t, d, causal):
    q, k, v = _r(b, hq, t, d), _r(b, hkv, t, d), _r(b, hkv, t, d)
    out = flash_attention(q, k, v, causal=causal, bq=32, bk=32)
    np.testing.assert_allclose(out, ref.attention_ref(q, k, v, causal=causal),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("b,hq,hkv,d,ps,pp", [(2, 4, 2, 32, 8, 4),
                                              (3, 8, 2, 64, 16, 6),
                                              (1, 4, 4, 128, 32, 3)])
def test_decode_attention_sweep(b, hq, hkv, d, ps, pp):
    p_total = b * pp + 2
    q = _r(b, hq, d)
    kp, vp = _r(p_total, ps, hkv, d), _r(p_total, ps, hkv, d)
    pt = jnp.asarray(RNG.permutation(p_total)[: b * pp].reshape(b, pp),
                     jnp.int32)
    lengths = jnp.asarray(RNG.integers(1, ps * pp, b), jnp.int32)
    out = decode_attention(q, kp, vp, pt, lengths)
    want = ref.decode_attention_ref(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 4), st.integers(1, 3), st.integers(1, 48),
       st.integers(1, 24), st.integers(1, 8))
def test_spmm_property(db, kb, n, d, kk):
    """Property: SpMM(sum) == dense one-hot matmul for any shape."""
    h = _r(n, 8)
    nbr = jnp.asarray(RNG.integers(0, n, (d, kk)), jnp.int32)
    mask = jnp.asarray(RNG.integers(0, 2, (d, kk)), jnp.float32)
    got = spmm(h, nbr, mask, mode="sum", bd=8 * db)
    dense = (jax.nn.one_hot(nbr, n) * mask[..., None]).sum(1) @ h
    np.testing.assert_allclose(got, dense, rtol=1e-4, atol=1e-4)
