"""Per-kernel validation vs the pure-jnp oracles: shape/dtype sweeps +
hypothesis property tests (on CPU the platform picks interpret mode, which
executes the kernel body as XLA ops)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.objective import mu_b_exact_value_and_grad
from repro.kernels.mpad_pairwise import (mu_kernel_value_and_grad,
                                         pairwise_stats_pallas,
                                         pairwise_stats_ref)
from repro.kernels.knn_topk import knn_ref, knn_topk_pallas


# ------------------------------------------------------ mpad_pairwise

@pytest.mark.parametrize("n,block", [(64, 64), (96, 32), (257, 64),
                                     (512, 128), (100, 256)])
def test_pairwise_stats_shapes(n, block):
    p = jax.random.normal(jax.random.key(n), (n,))
    tau = jnp.float32(0.5)
    c_r, s_r, co_r = pairwise_stats_ref(p, tau)
    c_k, s_k, co_k = pairwise_stats_pallas(p, tau, block_i=block,
                                           block_j=block)
    assert int(c_r) == int(c_k)
    np.testing.assert_allclose(float(s_r), float(s_k), rtol=1e-4)
    np.testing.assert_allclose(co_r, co_k, atol=1e-5)


@pytest.mark.parametrize("scale", [1e-3, 1.0, 1e3])
def test_pairwise_stats_scales(scale):
    """Scale invariance of the counting rule (f32 dynamic range sweep)."""
    p = jax.random.normal(jax.random.key(1), (128,)) * scale
    tau = jnp.float32(0.3 * scale)
    c_r, s_r, co_r = pairwise_stats_ref(p, tau)
    c_k, s_k, co_k = pairwise_stats_pallas(p, tau, block_i=64, block_j=64)
    assert int(c_r) == int(c_k)
    np.testing.assert_allclose(co_r, co_k, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(10, 150), st.floats(0.01, 3.0), st.integers(0, 10**6))
def test_pairwise_stats_property(n, tau, seed):
    p = jax.random.normal(jax.random.key(seed), (n,))
    c_r, s_r, co_r = pairwise_stats_ref(p, jnp.float32(tau))
    c_k, s_k, co_k = pairwise_stats_pallas(p, jnp.float32(tau),
                                           block_i=64, block_j=64)
    assert int(c_r) == int(c_k)
    np.testing.assert_allclose(float(s_r), float(s_k), rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(co_r, co_k, atol=1e-5)


@pytest.mark.parametrize("b", [20.0, 80.0])
def test_kernel_mu_matches_exact_oracle(b):
    x = jax.random.normal(jax.random.key(2), (200, 12))
    w = jax.random.normal(jax.random.key(3), (12,))
    w = w / jnp.linalg.norm(w)
    ve, ge = mu_b_exact_value_and_grad(w, x, b=b)
    vk, gk = mu_kernel_value_and_grad(w, x, b=b, block=64)
    np.testing.assert_allclose(float(ve), float(vk), rtol=1e-5)
    np.testing.assert_allclose(ge, gk, rtol=1e-3, atol=1e-5)


# ----------------------------------------------------------- knn_topk

@pytest.mark.parametrize("q,n,d,k,bq,bn", [
    (32, 200, 8, 5, 32, 64), (130, 1000, 32, 10, 64, 128),
    (64, 64, 4, 16, 64, 64), (7, 333, 17, 3, 32, 128)])
def test_knn_topk_shapes(q, n, d, k, bq, bn):
    qv = jax.random.normal(jax.random.key(q), (q, d))
    xv = jax.random.normal(jax.random.key(n), (n, d))
    d_k, i_k = knn_topk_pallas(qv, xv, k, block_q=bq, block_n=bn)
    d_r, i_r = knn_ref(qv, xv, k)
    np.testing.assert_array_equal(np.sort(np.asarray(i_k), 1),
                                  np.sort(np.asarray(i_r), 1))
    np.testing.assert_allclose(d_k, d_r, rtol=1e-4, atol=1e-4)


def test_knn_topk_bf16_inputs():
    qv = jax.random.normal(jax.random.key(0), (32, 16)).astype(jnp.bfloat16)
    xv = jax.random.normal(jax.random.key(1), (128, 16)).astype(jnp.bfloat16)
    d_k, i_k = knn_topk_pallas(qv, xv, 5, block_q=32, block_n=64)
    d_r, i_r = knn_ref(qv.astype(jnp.float32), xv.astype(jnp.float32), 5)
    # bf16 distance ties can permute ids; require >=80% id agreement
    agree = (np.sort(np.asarray(i_k), 1) == np.sort(np.asarray(i_r), 1)).mean()
    assert agree > 0.8


@settings(max_examples=10, deadline=None)
@given(st.integers(5, 60), st.integers(20, 200), st.integers(2, 12),
       st.integers(1, 8), st.integers(0, 10**6))
def test_knn_topk_property(q, n, d, k, seed):
    k = min(k, n)
    qv = jax.random.normal(jax.random.key(seed), (q, d))
    xv = jax.random.normal(jax.random.key(seed + 1), (n, d))
    d_k, i_k = knn_topk_pallas(qv, xv, k, block_q=32, block_n=64)
    d_r, i_r = knn_ref(qv, xv, k)
    np.testing.assert_array_equal(np.sort(np.asarray(i_k), 1),
                                  np.sort(np.asarray(i_r), 1))
    # distances ascending
    assert bool(jnp.all(jnp.diff(d_k, axis=1) >= -1e-6))
