"""Scan-path acceptance tests: uint8 codes end-to-end, the
nprobe-proportional compact scan, and the re-rank candidate pre-filter.

The speed paths this PR adds are all gated on BIT-IDENTICAL results — the
narrow code dtype, the posting-mass-capped gather, and the certified
pre-filter may change what the program reads and how wide it runs, never
what it returns. Every test here asserts ``array_equal`` (not allclose) on
ids AND distances against the reference path: jnp vs kernel backends,
1/2/8 devices, the streaming live-mask path, and a property sweep for the
pre-filter.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.search import SearchEngine, ServeConfig
from repro.search.ivfpq import ivfpq_adc_scan, ivfpq_compact_scan
from repro.search.registry import Index
from repro.search.serve import search_fn, sharded_search_fn

N, DIM, K = 601, 32, 10


def _data(seed=0, n=N, d=DIM):
    """Outlier-skewed corpus: ~40% of rows pile into one cluster, the kind
    of cell-size skew the compact scan exists for (the engine only engages
    it when the capped gather is well under the padded width)."""
    key = jax.random.key(seed)
    centers = jax.random.normal(key, (12, d)) * 2
    lab = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, 12)
    heavy = jax.random.uniform(jax.random.fold_in(key, 3), (n,)) < 0.4
    lab = jnp.where(heavy, 0, lab)
    return centers[lab] + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 2), (n, d))


def _queries(nq=24, seed=9):
    x = _data()
    return x[:nq] + 0.02 * jax.random.normal(jax.random.key(seed), (nq, DIM))


_ENGINES = {}


def _engine(**kw):
    """One ivfpq build per knob set (k-means train is the slow part)."""
    key = tuple(sorted(kw.items()))
    if key not in _ENGINES:
        cfg = ServeConfig(index="ivfpq", rerank=64, nlist=16, nprobe=8,
                          pq_subspaces=8, pq_centroids=64, **kw)
        _ENGINES[key] = SearchEngine(_data(), cfg)
    return _ENGINES[key]


def _as_int32_state(state):
    """The same built index with the stored codes widened to int32 — the
    pre-PR storage. Both widths must flow through every scan unchanged."""
    ix = state.index.payload
    wide = ix._replace(codes=ix.codes.astype(jnp.int32),
                       codes_cell=ix.codes_cell.astype(jnp.int32))
    return state._replace(index=Index("ivfpq", wide))


def _assert_bit_identical(a, b):
    (da, ia), (db, ib) = a, b
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    np.testing.assert_array_equal(np.asarray(da), np.asarray(db))


def _assert_same_ids(a, b):
    """Ids bit-identical; distances to float ULPs. The pre-filtered
    re-rank gathers a NARROWER candidate tensor, so XLA may vectorize the
    per-row feature reduction differently — same candidates, same math,
    reduction-order ULP wiggle on the returned distance."""
    (da, ia), (db, ib) = a, b
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    np.testing.assert_allclose(np.asarray(da), np.asarray(db),
                               rtol=1e-6, atol=1e-6)


# --- uint8 end-to-end ---------------------------------------------------------

def test_codes_stored_uint8():
    """K <= 256 builds store byte codes (row-major and cell-major mirrors)
    and the per-row reconstruction-error bound the pre-filter consumes."""
    ix = _engine().state.index.payload
    assert ix.codes.dtype == jnp.uint8
    assert ix.codes_cell.dtype == jnp.uint8
    assert ix.rerr.dtype == jnp.float32
    assert bool(jnp.all(ix.rerr >= 0))


@pytest.mark.kernels
@pytest.mark.parametrize("lut", ("f32", "bf16", "int8"))
@pytest.mark.parametrize("backend", ("jnp", "kernel"))
def test_uint8_vs_int32_parity(backend, lut):
    eng = _engine()
    q = _queries()
    kw = dict(nprobe=8, rerank=64, backend=backend, lut_dtype=lut)
    _assert_bit_identical(search_fn(eng.state, q, K, **kw),
                          search_fn(_as_int32_state(eng.state), q, K, **kw))


@pytest.mark.multidevice
@pytest.mark.parametrize("shards", (1, 2, 8))
def test_uint8_vs_int32_sharded_parity(shards):
    if jax.device_count() < shards:
        pytest.skip(f"needs {shards} devices (run under XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={shards})")
    from repro.parallel.engine import shard_engine
    mesh = jax.make_mesh((shards,), ("data",),
                         devices=jax.devices()[:shards])
    eng = _engine()
    q = _queries()
    kw = dict(nprobe=8, rerank=64, backend="jnp", lut_dtype="f32")
    s8 = shard_engine(eng.state, mesh)
    s32 = shard_engine(_as_int32_state(eng.state), mesh)
    _assert_bit_identical(
        sharded_search_fn(s8, q, K, mesh=mesh, axis="data", **kw),
        sharded_search_fn(s32, q, K, mesh=mesh, axis="data", **kw))


@pytest.mark.stream
def test_uint8_vs_int32_streaming_parity():
    """The tombstone-masked base scan consumes stored-width codes too:
    upserts + deletes (a live mask with real holes), then search the same
    store with codes widened to int32."""
    from repro.search.segments import StreamConfig
    from repro.search.stream import stream_search_fn
    cfg = ServeConfig(index="ivfpq", rerank=64, nlist=16, nprobe=8,
                      pq_subspaces=8, pq_centroids=64,
                      stream=StreamConfig(delta_capacity=64))
    eng = SearchEngine(_data(), cfg)
    eng.upsert(np.arange(N, N + 16), _data(seed=3, n=16))
    eng.delete(np.arange(0, 40, 3))
    assert eng.store.codes_cell.dtype == jnp.uint8
    wide = eng.store._replace(
        codes=eng.store.codes.astype(jnp.int32),
        codes_cell=eng.store.codes_cell.astype(jnp.int32))
    q = _queries()
    kw = dict(nprobe=8, rerank=64, backend="jnp", lut_dtype="f32")
    _assert_bit_identical(
        stream_search_fn(eng.store, eng.frozen, q, K, **kw),
        stream_search_fn(wide, eng.frozen, q, K, **kw))


# --- nprobe-proportional compact scan ----------------------------------------

@pytest.mark.parametrize("lut", ("f32", "bf16", "int8"))
@pytest.mark.parametrize("backend", ("jnp", "kernel"))
def test_compact_scan_bit_identical(backend, lut):
    """The capped, prefix-sum-indexed gather must reproduce the padded
    scan exactly: same candidates in the same enumeration order, so even
    top-k tie-breaks agree."""
    ix = _engine().state.index.payload
    q = _queries()
    cap = _engine()._scan_cap(8)
    assert cap > 0, "test corpus should have skewed cells"
    d1, i1 = ivfpq_adc_scan(ix.centroids, ix.lists, ix.codes_cell,
                            ix.bias_cell, ix.lut_w, ix.cbnorm, ix.codebooks,
                            q, 64, 8, backend, lut)
    d2, i2 = ivfpq_compact_scan(ix.centroids, ix.lists, ix.codes_cell,
                                ix.bias_cell, ix.lut_w, ix.cbnorm,
                                ix.codebooks, q, 64,
                                nprobe=8, scan_cap=cap, backend=backend,
                                lut_dtype=lut)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_engine_compact_path_matches_defaults():
    """End to end: small buckets route through the compact scan
    (``compact_batch``) plus the opt-in pre-filter (``prefilter_batch``)
    and must return exactly what the default wide program returns —
    across the whole small-batch range."""
    eng = _engine()
    assert eng._scan_cap(8) > 0
    for nq in (1, 3, 8, 24, 64):
        q = _queries(nq=nq, seed=100 + nq)
        eng.config = dataclasses.replace(eng.config, compact_batch=64,
                                         prefilter_batch=64)
        fast = eng.search(q, K)
        eng.config = dataclasses.replace(eng.config, compact_batch=0,
                                         prefilter_batch=0)
        slow = eng.search(q, K)
        _assert_same_ids(fast, slow)


def test_scan_cap_covers_worst_case():
    """The cached cap is a certified upper bound on any query's probed
    posting mass (sum of the nprobe largest cells), so the capped gather
    can never truncate."""
    eng = _engine()
    ix = eng.state.index.payload
    lens = np.asarray(jnp.sum(ix.lists >= 0, axis=1))
    for nprobe in (1, 4, 8, 16):
        cap = eng._scan_cap(nprobe)
        if cap:
            assert cap >= np.sort(lens)[-nprobe:].sum()
            assert cap % 128 == 0


# --- re-rank candidate pre-filter --------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 10), st.sampled_from(
    ["f32", "bf16", "int8"]))
def test_prefilter_never_drops_a_true_topk_id(seed, k, lut):
    """Property: for any queries, k, and LUT width, the pre-filtered
    re-rank returns exactly the ids and distances of the full-width
    re-rank — i.e. the certified threshold never discards a true top-k
    member (ties included)."""
    eng = _engine()
    q = _data()[:8] + 0.1 * jax.random.normal(jax.random.key(seed), (8, DIM))
    kw = dict(nprobe=8, rerank=64, backend="jnp", lut_dtype=lut)
    r_s = max(2 * k, 32)
    _assert_same_ids(
        search_fn(eng.state, q, k, prefilter=r_s, **kw),
        search_fn(eng.state, q, k, prefilter=0, **kw))


def test_prefilter_requires_scan_space_eq_rerank_space():
    """With a Reduce stage the scan distance bounds live in the reduced
    space and certify nothing about the re-rank space: search_fn must
    refuse, and the engine must not engage the pre-filter."""
    from repro.core import MPADConfig
    eng = _engine(target_dim=8, mpad=MPADConfig(m=8, iters=16),
                  fit_sample=512, prefilter_batch=64)
    with pytest.raises(ValueError, match="prefilter"):
        search_fn(eng.state, _queries(), K, nprobe=8, rerank=64,
                  prefilter=32)
    # prefilter_batch is set but target_dim forces it off: compact only
    d, ids = eng.search(_queries(), K)
    eng.config = dataclasses.replace(eng.config, compact_batch=0)
    _assert_same_ids((d, ids), eng.search(_queries(), K))


def test_stream_and_sharded_reject_fast_paths():
    """The fast paths are single-device read-only by contract."""
    from repro.search.segments import StreamConfig
    from repro.search.stream import stream_search_fn
    cfg = ServeConfig(index="ivfpq", rerank=64, nlist=16, nprobe=8,
                      pq_subspaces=8, pq_centroids=64,
                      stream=StreamConfig(delta_capacity=64))
    eng = SearchEngine(_data(), cfg)
    with pytest.raises(ValueError, match="scan_cap/prefilter"):
        stream_search_fn(eng.store, eng.frozen, _queries(), K, scan_cap=128)
    with pytest.raises(ValueError, match="scan_cap/prefilter"):
        stream_search_fn(eng.store, eng.frozen, _queries(), K, prefilter=32)
