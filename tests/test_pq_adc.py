"""Parity tests for the fused ADC-scan Pallas kernels against the pure-jnp
oracles in ref.py (on CPU the platform picks interpret mode, which executes
the kernel body as XLA ops; ``tests/test_tpu_compile.py`` compiles the same
kernels for a v5e)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.pq_adc import (dequantize_lut, lut_error_bound,
                                  pq_adc_gather_scores_onehot,
                                  pq_adc_gather_scores_ref,
                                  pq_adc_gather_topk_pallas,
                                  pq_adc_gather_topk_ref, pq_adc_scores_ref,
                                  pq_adc_topk_pallas, pq_adc_topk_ref,
                                  quantize_lut)
from repro.search import ivfpq
from repro.search.pq import build_pq, pq_search

pytestmark = pytest.mark.kernels


def _tables_codes(key, nq, n, m, kc):
    tables = jax.random.uniform(jax.random.fold_in(key, 0), (nq, m, kc))
    codes = jax.random.randint(jax.random.fold_in(key, 1), (n, m), 0, kc)
    return tables, codes


@pytest.mark.parametrize("nq,n,m,kc,bq,bn", [
    (17, 300, 4, 64, 8, 128),        # ragged Q and N, small codebook
    (64, 1000, 8, 256, 32, 256),     # byte-code shape, ragged N
    (128, 512, 16, 128, 128, 512),   # exact-block shape
])
def test_shared_kernel_matches_ref(nq, n, m, kc, bq, bn):
    tables, codes = _tables_codes(jax.random.key(0), nq, n, m, kc)
    d_ref, i_ref = pq_adc_topk_ref(tables, codes, 10)
    d_k, i_k = pq_adc_topk_pallas(tables, codes, 10, block_q=bq, block_n=bn)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_ref), atol=1e-4)
    # ids can legitimately differ on near-ties; check each returned id's
    # true score is within tolerance of the oracle's at the same rank
    scores = np.asarray(pq_adc_scores_ref(tables, codes))
    picked = np.take_along_axis(scores, np.asarray(i_k), axis=1)
    np.testing.assert_allclose(picked, np.asarray(d_ref), atol=1e-4)


@pytest.mark.parametrize("nq,c,m,kc,bq,bn", [
    (9, 200, 4, 32, 4, 64),
    (33, 513, 8, 128, 8, 128),
])
def test_gather_kernel_matches_ref(nq, c, m, kc, bq, bn):
    key = jax.random.key(1)
    tables = jax.random.uniform(jax.random.fold_in(key, 0), (nq, m, kc))
    codes = jax.random.randint(jax.random.fold_in(key, 1), (nq, c, m), 0, kc)
    base = jax.random.uniform(jax.random.fold_in(key, 2), (nq, c))
    base = base.at[:, -5:].set(jnp.inf)          # masked posting-list pads
    d_ref, _ = pq_adc_gather_topk_ref(tables, codes, base, 12)
    d_k, _ = pq_adc_gather_topk_pallas(tables, codes, base, 12,
                                       block_q=bq, block_n=bn)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_ref), atol=1e-4)


def test_masked_pads_never_surface():
    """All-but-k candidates masked: the kernel must return exactly the
    unmasked slots, in distance order."""
    nq, c, m, kc = 4, 96, 4, 16
    key = jax.random.key(2)
    tables = jax.random.uniform(jax.random.fold_in(key, 0), (nq, m, kc))
    codes = jax.random.randint(jax.random.fold_in(key, 1), (nq, c, m), 0, kc)
    base = jnp.full((nq, c), jnp.inf)
    keep = jnp.array([3, 17, 40, 77])
    base = base.at[:, keep].set(0.0)
    d_k, i_k = pq_adc_gather_topk_pallas(tables, codes, base, 4,
                                         block_q=4, block_n=32)
    assert np.isfinite(np.asarray(d_k)).all()
    np.testing.assert_array_equal(np.sort(np.asarray(i_k), axis=1),
                                  np.broadcast_to(np.asarray(keep), (nq, 4)))


# --- quantized LUT path (lut_dtype="bf16" | "int8") -------------------------

@pytest.mark.parametrize("lut_dtype,atol", [("bf16", 1e-2), ("int8", 1e-3)])
def test_shared_kernel_quantized_matches_ref(lut_dtype, atol):
    """Kernel and ref score through the same quantized tables, so they must
    agree up to f32 summation order — the quantization error itself cancels."""
    tables, codes = _tables_codes(jax.random.key(7), 33, 500, 8, 64)
    tables = tables * 5.0
    d_ref, _ = pq_adc_topk_ref(tables, codes, 10, lut_dtype=lut_dtype)
    d_k, i_k = pq_adc_topk_pallas(tables, codes, 10, block_q=8, block_n=128,
                                  lut_dtype=lut_dtype)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_ref), atol=atol)
    scores = np.asarray(pq_adc_scores_ref(tables, codes, lut_dtype))
    picked = np.take_along_axis(scores, np.asarray(i_k), axis=1)
    np.testing.assert_allclose(picked, np.asarray(d_ref), atol=atol)


@pytest.mark.parametrize("lut_dtype,atol", [("bf16", 1e-2), ("int8", 1e-3)])
def test_gather_kernel_quantized_matches_ref(lut_dtype, atol):
    key = jax.random.key(8)
    tables = jax.random.uniform(jax.random.fold_in(key, 0), (9, 8, 64)) * 5.0
    codes = jax.random.randint(jax.random.fold_in(key, 1), (9, 200, 8), 0, 64)
    base = jax.random.uniform(jax.random.fold_in(key, 2), (9, 200))
    base = base.at[:, -5:].set(jnp.inf)
    d_ref, _ = pq_adc_gather_topk_ref(tables, codes, base, 12,
                                      lut_dtype=lut_dtype)
    d_k, _ = pq_adc_gather_topk_pallas(tables, codes, base, 12, block_q=4,
                                       block_n=64, lut_dtype=lut_dtype)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_ref), atol=atol)


# --- one-hot select-reduce lowering of the gathered-codes scorer -----------

@pytest.mark.parametrize("given", [False, True], ids=["derived", "given"])
@pytest.mark.parametrize("nq,kc", [(1, 16), (1, 256), (5, 16), (5, 256),
                                   (64, 16), (64, 256)])
@pytest.mark.parametrize("code_dtype", [jnp.uint8, jnp.int32],
                         ids=["uint8", "int32"])
@pytest.mark.parametrize("lut_dtype", ["f32", "bf16", "int8"])
def test_onehot_scores_match_gather(lut_dtype, code_dtype, nq, kc, given):
    """The one-hot select-reduce selects each table entry exactly, so it
    differs from the gather only in the order of the (M+1)-term sum: at
    most M float32 ulps of sum |term| (none on the int8 grid, whose sums
    are exact). +inf bases stay +inf, and top-k ids agree wherever the
    k+1 best scores are not tied within that bound."""
    m, c, k = 16, 97, 10
    key = jax.random.key(20 + nq + kc)
    tables = jax.random.normal(jax.random.fold_in(key, 0), (nq, m, kc)) * 3
    codes = jax.random.randint(jax.random.fold_in(key, 1), (nq, c, m), 0,
                               kc).astype(code_dtype)
    base = jax.random.uniform(jax.random.fold_in(key, 2), (nq, c)) * 10
    base = base.at[:, -5:].set(jnp.inf)          # masked posting-list pads
    center = scale = None
    if given:
        center = jnp.mean(tables, axis=2)
        if lut_dtype == "int8":                  # a looser certified bound
            scale = 1.25 * jnp.max(jnp.abs(tables - center[:, :, None]),
                                   axis=(1, 2)) / 127.0
    args = (tables, codes, base, lut_dtype, scale, center)
    want = np.asarray(pq_adc_gather_scores_ref(*args))
    got = np.asarray(pq_adc_gather_scores_onehot(*args))
    assert np.isinf(got[:, -5:]).all() and np.isfinite(got[:, :-5]).all()
    want, got = want[:, :-5], got[:, :-5]
    if lut_dtype == "int8":                      # exact integer sums
        np.testing.assert_array_equal(got, want)
    # sum |term| from the tables before the snap, which moves a bf16
    # entry by under 1%
    idx = np.asarray(codes[:, :-5]).astype(np.int64).transpose(0, 2, 1)
    t = np.asarray(tables)
    if center is not None and lut_dtype != "f32":
        t = t - np.asarray(center)[:, :, None]
    mag = (np.abs(np.take_along_axis(t, idx, axis=2)).sum(1)
           + np.abs(np.asarray(base[:, :-5])))
    tol = m * np.spacing((1.01 * mag).astype(np.float32))
    assert (np.abs(got - want) <= tol).all()
    order = np.argsort(want, axis=1, kind="stable")
    gaps = np.diff(np.take_along_axis(want, order[:, :k + 1], axis=1), axis=1)
    untied = (gaps > 2 * np.take_along_axis(tol, order[:, :k + 1],
                                            axis=1)[:, 1:]).all(axis=1)
    assert untied.any()
    ids_w = np.asarray(jax.lax.top_k(-jnp.asarray(want), k)[1])
    ids_g = np.asarray(jax.lax.top_k(-jnp.asarray(got), k)[1])
    np.testing.assert_array_equal(ids_g[untied], ids_w[untied])


def test_adc_lowering_follows_platform(monkeypatch):
    """Gather on a CPU; one-hot when the default backend is a TPU; the
    ``qpad.adc_*`` scope names the lowering in the compiled program."""
    assert jax.default_backend() == "cpu"
    assert ivfpq._adc_lowering() == "gather"
    args = (jnp.ones((2, 4, 16)), jnp.zeros((2, 8, 4), jnp.uint8),
            jnp.zeros((2, 8)))

    def text():
        return jax.jit(lambda t, c, b: ivfpq._adc_scores(
            t, c, b, "f32", None, None)).lower(*args).as_text(
                debug_info=True)

    assert "qpad.adc_gather" in text() and "qpad.adc_onehot" not in text()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ivfpq._adc_lowering() == "onehot"
    assert "qpad.adc_onehot" in text() and "qpad.adc_gather" not in text()


def test_int8_scale_round_trip():
    """quantize -> dequantize must stay within scale/2 per entry, scales are
    strictly positive, and the int8 grid is fully symmetric (|q| <= 127)."""
    tables = jax.random.normal(jax.random.key(9), (12, 8, 64)) * 7.0
    qt, scale = quantize_lut(tables, "int8")
    assert qt.dtype == jnp.int8
    assert float(jnp.min(scale)) > 0.0
    assert int(jnp.max(jnp.abs(qt.astype(jnp.int32)))) <= 127
    rt = dequantize_lut(qt, scale)
    err = jnp.abs(rt - tables)
    assert float(jnp.max(err - scale[:, None, None] / 2)) <= 1e-6
    # degenerate all-zero table: scale must not collapse to 0/NaN
    qt0, scale0 = quantize_lut(jnp.zeros((2, 4, 8)), "int8")
    assert float(jnp.min(scale0)) > 0.0
    assert not np.isnan(np.asarray(dequantize_lut(qt0, scale0))).any()


@pytest.mark.parametrize("lut_dtype", ["bf16", "int8"])
def test_quantized_scores_within_error_bound(lut_dtype):
    """|quantized ADC score - f32 ADC score| <= lut_error_bound per query."""
    tables, codes = _tables_codes(jax.random.key(10), 16, 300, 8, 32)
    tables = (tables - 0.5) * 9.0
    s_f32 = np.asarray(pq_adc_scores_ref(tables, codes))
    s_q = np.asarray(pq_adc_scores_ref(tables, codes, lut_dtype))
    bound = np.asarray(lut_error_bound(tables, lut_dtype))[:, None]
    assert (np.abs(s_q - s_f32) <= bound + 1e-5).all()


@pytest.mark.parametrize("lut_dtype", ["f32", "bf16", "int8"])
def test_pq_search_backends_agree_per_lut_dtype(lut_dtype):
    """jnp and kernel backends are parity oracles at every LUT precision."""
    key = jax.random.key(11)
    x = jax.random.normal(jax.random.fold_in(key, 0), (600, 32))
    q = jax.random.normal(jax.random.fold_in(key, 1), (40, 32))
    idx = build_pq(jax.random.fold_in(key, 2), x, m_subspaces=4,
                   n_centroids=64)
    d_j, _ = pq_search(idx, q, 10, backend="jnp", lut_dtype=lut_dtype)
    d_k, _ = pq_search(idx, q, 10, backend="kernel", lut_dtype=lut_dtype)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_j), atol=1e-3)


def test_pq_search_rejects_unknown_lut_dtype():
    key = jax.random.key(12)
    x = jax.random.normal(key, (200, 16))
    idx = build_pq(key, x, m_subspaces=4, n_centroids=32)
    with pytest.raises(ValueError, match="lut_dtype"):
        pq_search(idx, x[:4], 5, lut_dtype="fp4")


def test_pq_search_kernel_backend_matches_jnp():
    key = jax.random.key(3)
    x = jax.random.normal(jax.random.fold_in(key, 0), (600, 32))
    q = jax.random.normal(jax.random.fold_in(key, 1), (40, 32))
    idx = build_pq(jax.random.fold_in(key, 2), x, m_subspaces=4,
                   n_centroids=64)
    d_j, _ = pq_search(idx, q, 10, backend="jnp")
    d_k, _ = pq_search(idx, q, 10, backend="kernel")
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_j), atol=1e-4)


def test_pq_search_rejects_unknown_backend():
    key = jax.random.key(4)
    x = jax.random.normal(key, (200, 16))
    idx = build_pq(key, x, m_subspaces=4, n_centroids=32)
    with pytest.raises(ValueError, match="backend"):
        pq_search(idx, x[:4], 5, backend="cuda")
