"""The index build in row blocks against its plain unblocked reference.

``ivf.kmeans`` and ``pq.build_pq`` walk the corpus in row blocks sized from
``ivf.BUILD_TILE_BYTES``; ``ivf.kmeans_ref`` and ``pq.build_pq_ref`` keep
the whole-corpus bodies. With the budget patched small enough that a test
corpus takes several blocks, the blocked build must give:

* centroids and codebooks within 1e-5 relative (float32 sums accumulate in
  another order), exactly equal where the data make every sum exact;
* identical assignments and codes on data without near-ties;
* from ``build_ivfpq``, identical ``lists`` and ``codes_cell``, and
  ``bias_cell`` within float32 rounding of the centroids it is built from;
* through ``SearchEngine``, the same ids and the same recall as an engine
  built in one block.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import make_clustered
from repro.search import (SearchEngine, ServeConfig, build_ivfpq, ivf,
                          knn_search_blocked, recall_at_k)
from repro.search.pq import build_pq, build_pq_ref

RTOL = 1e-5            # float32 sums of a few thousand rows, reordered


def _tile_for(rows: int, k: int) -> int:
    """The ``BUILD_TILE_BYTES`` that gives blocks of ``rows`` rows (a
    multiple of 8) against ``k`` centroids."""
    return 4 * k * rows


def _clustered(n, d, clusters=24, seed=0):
    x, _ = make_clustered(jax.random.key(seed), n, 1, d,
                          n_clusters=clusters, spread=0.2, center_scale=3.0)
    return x


def _grid(n, d, points=3, seed=0):
    """``points`` distinct small-integer vectors repeated over ``n`` rows:
    every sum is exact in float32, and with more clusters than points some
    clusters are empty."""
    rng = np.random.default_rng(seed)
    base = rng.integers(-4, 5, size=(points, d)).astype(np.float32)
    return jnp.asarray(base[rng.integers(0, points, size=n)])


@pytest.mark.parametrize("case,n,d,nlist,block", [
    ("N not a multiple of the block", 1001, 16, 16, 64),
    ("nlist larger than the block", 800, 8, 64, 16),
    ("one block", 496, 16, 16, 496),
])
def test_kmeans_matches_the_unblocked_reference(monkeypatch, case, n, d,
                                                nlist, block):
    x = _clustered(n, d)
    monkeypatch.setattr(ivf, "BUILD_TILE_BYTES", _tile_for(block, nlist))
    assert ivf.block_rows(n, nlist) == block
    key = jax.random.key(3)
    ref = np.asarray(ivf.kmeans_ref(key, x, nlist))
    got = np.asarray(ivf.kmeans(key, x, nlist))
    np.testing.assert_allclose(got, ref, rtol=RTOL,
                               atol=RTOL * np.abs(ref).max())
    # the final assignment, in blocks, is the whole-corpus argmin
    want = np.asarray(jnp.argmin(ivf.sq_dists(x, jnp.asarray(ref)), axis=1))
    np.testing.assert_array_equal(
        np.asarray(ivf.assign_cells(x, jnp.asarray(ref))), want)
    np.testing.assert_array_equal(
        np.asarray(ivf.assign_cells(x, jnp.asarray(got))), want)


def test_kmeans_keeps_empty_clusters_as_the_reference_does(monkeypatch):
    x = _grid(700, 8, points=3)
    monkeypatch.setattr(ivf, "BUILD_TILE_BYTES", _tile_for(64, 8))
    key = jax.random.key(1)
    ref = np.asarray(ivf.kmeans_ref(key, x, 8))
    got = np.asarray(ivf.kmeans(key, x, 8))
    counts = np.bincount(np.asarray(ivf.assign_cells(x, jnp.asarray(ref))),
                         minlength=8)
    assert (counts == 0).any()          # the case under test happened
    np.testing.assert_array_equal(got, ref)     # integer sums: exact


@pytest.mark.parametrize("centroids,dtype", [(64, np.uint8),
                                             (300, np.int32)])
def test_build_pq_matches_the_unblocked_reference(monkeypatch, centroids,
                                                  dtype):
    x = _grid(1203, 16, points=40)        # no near-ties between codewords
    monkeypatch.setattr(ivf, "BUILD_TILE_BYTES", _tile_for(40, centroids))
    key = jax.random.key(5)
    ref = build_pq_ref(key, x, m_subspaces=4, n_centroids=centroids)
    got = build_pq(key, x, m_subspaces=4, n_centroids=centroids)
    assert got.codes.dtype == ref.codes.dtype == dtype
    cb = np.asarray(ref.codebooks)
    np.testing.assert_allclose(np.asarray(got.codebooks), cb, rtol=RTOL,
                               atol=RTOL * np.abs(cb).max())
    np.testing.assert_array_equal(np.asarray(got.codes),
                                  np.asarray(ref.codes))
    np.testing.assert_allclose(np.asarray(got.lut_w), np.asarray(ref.lut_w),
                               rtol=RTOL, atol=RTOL * np.abs(cb).max())


@pytest.mark.parametrize("shards", [1, 4])
def test_build_ivfpq_layout_matches_one_block(monkeypatch, shards):
    x = _clustered(1500, 32)
    key = jax.random.key(2)
    one = build_ivfpq(key, x, nlist=16, m_subspaces=8, n_centroids=32,
                      shards=shards)
    monkeypatch.setattr(ivf, "BUILD_TILE_BYTES", _tile_for(48, 32))
    got = build_ivfpq(key, x, nlist=16, m_subspaces=8, n_centroids=32,
                      shards=shards)
    for name in ("lists", "codes", "codes_cell"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(one, name)),
                                      err_msg=name)
    assert got.codes_cell.dtype == one.codes_cell.dtype == np.uint8
    for name in ("centroids", "bias_cell", "bias", "rerr"):
        want = np.asarray(getattr(one, name))
        np.testing.assert_allclose(np.asarray(getattr(got, name)), want,
                                   rtol=RTOL, atol=RTOL * np.abs(want).max(),
                                   err_msg=name)


def test_blocked_engine_returns_the_same_ids(monkeypatch):
    x, q = make_clustered(jax.random.key(7), 3000, 64, 32, n_clusters=24,
                          spread=0.35, center_scale=1.5)
    cfg = ServeConfig(index="ivfpq", nlist=16, nprobe=4, pq_subspaces=8,
                      pq_centroids=64, rerank=40)
    one = SearchEngine(x, cfg)
    monkeypatch.setattr(ivf, "BUILD_TILE_BYTES", _tile_for(96, 16))
    blocked = SearchEngine(x, cfg)
    assert blocked.metrics().build.blocks == 32
    assert one.metrics().build.blocks == 1
    _, ids_one = one.search(q, 10)
    _, ids_blk = blocked.search(q, 10)
    np.testing.assert_array_equal(np.asarray(ids_blk), np.asarray(ids_one))
    _, truth = knn_search_blocked(q, x, 10)
    assert float(recall_at_k(ids_blk, truth)) == pytest.approx(
        float(recall_at_k(ids_one, truth)))
