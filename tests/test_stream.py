"""Streaming index subsystem: delta segments, tombstones, compaction.

The contracts pinned here:

* **freshness** — upserted rows are searchable immediately (served exactly
  from the delta), deletes take effect immediately on both layers;
* **equivalence** — any interleaving of upserts/deletes followed by
  ``compact()`` returns the same top-k as rebuilding the index from
  scratch on the surviving rows with the same frozen quantizers
  (``rebuild_state``), for every index kind and LUT dtype;
* **jit stability** — interleaved upsert/delete/search on a 16k-row
  corpus never recompiles after warmup (``SearchEngine.compile_count``
  pinned); capacity overflow is the one declared recompile point
  (``grow_count``) and stays correct.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MPADConfig
from repro.search import (SearchEngine, ServeConfig, StreamConfig,
                          rebuild_state, search_fn)

pytestmark = pytest.mark.stream

N, DIM, K = 600, 32, 10


def _data(seed=0, n=N, d=DIM):
    key = jax.random.key(seed)
    centers = jax.random.normal(key, (12, d)) * 2
    lab = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, 12)
    return centers[lab] + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 2), (n, d))


def _queries(nq=16):
    x = _data()
    return x[:nq] + 0.02 * jax.random.normal(jax.random.key(9), (nq, DIM))


def _cfg(index, lut="f32", target_dim=None, **stream_kw):
    stream_kw.setdefault("delta_capacity", 64)
    kw = dict(target_dim=target_dim, rerank=128, index=index,
              mpad=MPADConfig(m=8, iters=16) if target_dim else None,
              fit_sample=512, stream=StreamConfig(**stream_kw))
    # stage knobs only where the pipeline has the stage (dead knobs raise)
    if index in ("ivf", "ivfpq"):
        kw.update(nlist=12, nprobe=12)
    if index in ("pq", "ivfpq"):
        kw.update(pq_subspaces=8, pq_centroids=64, lut_dtype=lut)
    return ServeConfig(**kw)


def _engine(index, **kw):
    return SearchEngine(_data(), _cfg(index, **kw))


# --- freshness: the delta layer serves writes immediately --------------------

@pytest.mark.parametrize("index", ("flat", "ivf", "pq", "ivfpq"))
def test_fresh_stream_matches_static(index):
    """Before any write, the streaming engine is the static engine."""
    eng = _engine(index)
    static = SearchEngine(_data(), dataclasses.replace(eng.config,
                                                       stream=None))
    q = _queries()
    d1, i1 = eng.search(q, K)
    d2, i2 = static.search(q, K)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-5)


@pytest.mark.parametrize("index", ("flat", "ivfpq"))
def test_upsert_visible_immediately_and_exact(index):
    eng = _engine(index)
    q = _queries()
    new_ids = np.arange(N, N + q.shape[0])
    eng.upsert(new_ids, q)
    d, ids = eng.search(q, K)
    # each query's own upserted copy wins at distance ~0 — served exactly
    # from the delta, not through any quantizer
    np.testing.assert_array_equal(np.asarray(ids)[:, 0], new_ids)
    assert float(np.asarray(d)[:, 0].max()) < 1e-3


def test_upsert_overwrites_by_id():
    eng = _engine("ivfpq")
    q = _queries(4)
    far = 100.0 + jnp.zeros((4, DIM))
    eng.upsert(np.arange(N, N + 4), q)            # near the queries
    eng.upsert(np.arange(N, N + 4), far)          # same ids, far away
    _, ids = eng.search(q, K)
    assert not np.isin(np.arange(N, N + 4), np.asarray(ids)[:, 0]).any()
    # overwriting a BASE id tombstones the base copy
    base_id = int(np.asarray(eng.search(q[:1], 1)[1])[0, 0])
    eng.upsert(np.array([base_id]), far[:1])
    _, ids2 = eng.search(q[:1], K)
    assert base_id not in np.asarray(ids2)[0]


def test_delete_hides_base_and_delta_rows():
    eng = _engine("ivfpq")
    q = _queries(4)
    _, before = eng.search(q, K)
    top = np.asarray(before)[:, 0]
    eng.delete(top)                               # base rows
    _, after = eng.search(q, K)
    assert not np.isin(top, np.asarray(after)).any()
    eng.upsert(np.arange(N, N + 4), q)            # delta rows
    eng.delete(np.arange(N, N + 4))
    _, final = eng.search(q, K)
    assert not np.isin(np.arange(N, N + 4), np.asarray(final)).any()
    # deleting an absent id is a no-op
    eng.delete(np.array([10 ** 6]))
    _, again = eng.search(q, K)
    np.testing.assert_array_equal(np.asarray(final), np.asarray(again))


def test_reupsert_after_delete_resurfaces():
    eng = _engine("flat")
    q = _queries(2)
    eng.upsert(np.array([N, N + 1]), q)
    eng.delete(np.array([N, N + 1]))
    eng.upsert(np.array([N, N + 1]), q)
    _, ids = eng.search(q, K)
    np.testing.assert_array_equal(np.asarray(ids)[:, 0], [N, N + 1])


# --- equivalence: interleavings + compact == rebuild from scratch ------------

def _apply_random_ops(eng, rng, steps=8):
    """Random interleaving of upserts (new ids + overwrites) and deletes;
    returns the surviving {id: vector} map."""
    alive = {i: np.asarray(_data()[i]) for i in range(N)}
    next_id = N
    for _ in range(steps):
        if rng.rand() < 0.6:
            b = rng.randint(1, 20)
            ids, vecs = [], []
            for _ in range(b):
                if alive and rng.rand() < 0.3:
                    i = int(rng.choice(list(alive)))
                else:
                    i, next_id = next_id, next_id + 1
                v = rng.randn(DIM).astype(np.float32)
                ids.append(i), vecs.append(v)
                alive[i] = v
            eng.upsert(np.array(ids), np.stack(vecs))
        else:
            ids = [int(i) for i in rng.choice(
                list(alive), size=min(rng.randint(1, 10), len(alive)),
                replace=False)]
            for i in ids:
                del alive[i]
            eng.delete(np.array(ids))
    return alive


@pytest.mark.parametrize("index,lut,target_dim", [
    ("flat", "f32", None), ("ivf", "f32", None), ("pq", "f32", None),
    ("ivfpq", "f32", None), ("flat", "f32", 8), ("ivfpq", "f32", 8),
    ("ivfpq", "int8", None), ("ivfpq", "int8", 8), ("pq", "int8", None),
])
@pytest.mark.parametrize("seed", (3, 7))
def test_interleaved_ops_then_compact_equals_rebuild(index, lut, target_dim,
                                                     seed):
    """The acceptance property: post-compaction streaming search returns
    the same top-k ids as a from-scratch rebuild over the surviving rows
    with the same frozen quantizers."""
    eng = SearchEngine(_data(), _cfg(index, lut=lut, target_dim=target_dim))
    rng = np.random.RandomState(seed)
    alive = _apply_random_ops(eng, rng)
    eng.compact()
    assert int(eng.store.delta_count) == 0
    surv_ids = np.array(sorted(alive))
    surv = jnp.asarray(np.stack([alive[i] for i in surv_ids]))
    oracle = rebuild_state(eng.frozen, surv, index=index)
    coded = index in ("pq", "ivfpq")
    q = _queries()
    d_r, i_r = search_fn(oracle, q, K, nprobe=12, rerank=128,
                         backend="jnp",
                         lut_dtype=lut if coded else "f32")
    ext_r = surv_ids[np.asarray(i_r)]
    d_s, i_s = eng.search(q, K)
    np.testing.assert_array_equal(np.sort(np.asarray(i_s), axis=1),
                                  np.sort(ext_r, axis=1))
    np.testing.assert_allclose(np.sort(np.asarray(d_s), axis=1),
                               np.sort(np.asarray(d_r), axis=1), atol=1e-4)


# --- jit stability: no recompiles after warmup -------------------------------

def test_interleaved_16k_never_recompiles_after_warmup():
    """The acceptance pin: a 16k-row streaming ivfpq engine serving an
    interleaved upsert/delete/search workload (including auto-compactions)
    holds its compile count constant after one warmup of each op."""
    n, d = 16384, DIM
    key = jax.random.key(0)
    centers = jax.random.normal(key, (64, d)) * 2
    lab = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, 64)
    x = centers[lab] + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 2), (n, d))
    eng = SearchEngine(x, ServeConfig(
        target_dim=None, rerank=64, index="ivfpq", nlist=64, nprobe=8,
        pq_subspaces=8, pq_centroids=256,
        stream=StreamConfig(delta_capacity=128, write_bucket=64,
                            row_capacity=n + 4096, cell_slack=2048)))
    q = jnp.asarray(x[:64])
    rng = np.random.RandomState(0)
    # warmup: one of each program (search bucket, write bucket, compact)
    eng.search(q, K)
    eng.upsert(np.arange(n, n + 32), rng.randn(32, d).astype(np.float32))
    eng.delete(np.arange(n, n + 8))
    eng.compact()
    eng.search(q, K)
    cc = eng.compile_count
    for step in range(40):                     # crosses the auto-compact
        eng.upsert(np.arange(n + 100 + 32 * step, n + 132 + 32 * step),
                   rng.randn(32, d).astype(np.float32))
        eng.delete(rng.randint(0, n, size=8).astype(np.int32))
        eng.search(q, K)
    assert eng.grow_count == 0
    assert eng.compile_count == cc, (cc, eng.compile_count)


def test_write_buckets_share_compilations():
    # delta_capacity high enough that the loop never auto-compacts
    eng = _engine("flat", write_bucket=32, delta_capacity=256)
    rng = np.random.RandomState(0)
    eng.upsert(np.array([N]), rng.randn(1, DIM).astype(np.float32))
    cc = eng.compile_count
    for b in (1, 5, 17, 32):                  # all inside the 32-bucket
        eng.upsert(np.arange(N, N + b), rng.randn(b, DIM).astype(np.float32))
        eng.delete(np.arange(N, N + b))
    assert eng.compile_count == cc + 1        # +1: the delete program


def test_delta_overflow_auto_compacts():
    """One upsert call larger than the delta capacity streams through in
    chunks with compactions in between — nothing is lost."""
    eng = _engine("ivfpq", delta_capacity=32)
    rng = np.random.RandomState(1)
    nb = 100
    vecs = rng.randn(nb, DIM).astype(np.float32)
    eng.upsert(np.arange(N, N + nb), vecs)
    _, ids = eng.search(jnp.asarray(vecs[:8]), 1)
    np.testing.assert_array_equal(np.asarray(ids)[:, 0],
                                  np.arange(N, N + 8))


def test_compact_overflow_grows_and_stays_correct():
    """Under-provisioned capacity: compaction detects the overflow, grows
    host-side (the declared recompile point), retries, and serves the
    same results as a generously provisioned engine."""
    rng = np.random.RandomState(2)
    vecs = rng.randn(80, DIM).astype(np.float32)
    tight = _engine("ivfpq", delta_capacity=64,
                    row_capacity=N + 8, cell_slack=2)
    roomy = _engine("ivfpq", delta_capacity=64,
                    row_capacity=N + 512, cell_slack=512)
    for eng in (tight, roomy):
        eng.upsert(np.arange(N, N + 80), vecs)
        eng.compact()
    assert tight.grow_count >= 1 and roomy.grow_count == 0
    q = _queries()
    _, i1 = tight.search(q, K)
    _, i2 = roomy.search(q, K)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_streaming_engine_releases_dense_state():
    """The StreamStore owns fresh copies of every database leaf, so the
    dense EngineState duplicates are released at init (no standing 2x);
    frozen quantizers and the caller's corpus array stay alive."""
    x = _data()
    eng = SearchEngine(x, _cfg("ivfpq"))
    assert eng.state is None
    assert not x.is_deleted()                       # caller-owned
    for leaf in jax.tree_util.tree_leaves(eng.frozen):
        assert not leaf.is_deleted()
    _, ids = eng.search(_queries(4), K)             # still serves
    assert np.asarray(ids).shape == (4, K)


def test_upsert_fn_reports_dropped_on_full_delta():
    """The raw (engine-less) write API signals overflow instead of
    silently losing rows."""
    from repro.search import upsert_fn
    eng = _engine("flat", delta_capacity=4)
    rng = np.random.RandomState(0)
    ids = jnp.arange(N + 100, N + 108, dtype=jnp.int32)
    vecs = jnp.asarray(rng.randn(8, DIM), jnp.float32)
    store, dropped = upsert_fn(eng.store, eng.frozen, ids, vecs)
    assert int(dropped) == 4                        # 4 fit, 4 reported lost
    assert int(store.delta_count) == 4


# --- config / guard rails ----------------------------------------------------

def test_stream_pq_kernel_backend_rejected():
    with pytest.raises(ValueError, match="pq_backend"):
        ServeConfig(index="pq", pq_backend="kernel",
                    stream=StreamConfig())


def test_streamconfig_validation():
    with pytest.raises(ValueError, match="delta_capacity"):
        StreamConfig(delta_capacity=0)
    with pytest.raises(ValueError, match="compact_threshold"):
        StreamConfig(compact_threshold=0.0)
    with pytest.raises(ValueError, match="write_bucket"):
        StreamConfig(write_bucket=0)


def test_streaming_after_shard_rejected():
    """streaming() must come before shard(): the store takes over the
    dense arrays, which would strand (or delete) the placed sharded
    state."""
    eng = SearchEngine(_data(), ServeConfig(target_dim=None))
    eng.shard(jax.make_mesh((1,), ("data",)))
    with pytest.raises(RuntimeError, match="BEFORE shard"):
        eng.streaming(StreamConfig())


def test_write_api_requires_stream_config():
    eng = SearchEngine(_data(), ServeConfig(target_dim=None))
    with pytest.raises(RuntimeError, match="read-only"):
        eng.upsert(np.array([0]), np.zeros((1, DIM), np.float32))
    with pytest.raises(RuntimeError, match="read-only"):
        eng.delete(np.array([0]))
    with pytest.raises(RuntimeError, match="read-only"):
        eng.compact()


def test_ivfpq_kernel_backend_streams():
    """The fused Pallas ADC-gather kernel serves the tombstone-masked scan
    (the mask rides the additive base term)."""
    eng = SearchEngine(_data(), dataclasses.replace(
        _cfg("ivfpq"), pq_backend="kernel"))
    ref = SearchEngine(_data(), _cfg("ivfpq"))
    q = _queries(8)
    rng = np.random.RandomState(3)
    vecs = rng.randn(16, DIM).astype(np.float32)
    for eng_ in (eng, ref):
        eng_.upsert(np.arange(N, N + 16), vecs)
        eng_.delete(np.arange(0, 20, 2))
    _, i1 = eng.search(q, K)
    _, i2 = ref.search(q, K)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
