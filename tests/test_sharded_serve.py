"""Mesh-sharded serving: the distributed top-k merge must be invisible.

``sharded_search_fn`` over ``shard_engine(state, mesh)`` returns exactly
the same neighbor ids (and distances, to fp tolerance) as the
single-device ``search_fn`` — for every index kind, both LUT dtypes, both
scoring backends, and 1 / 2 / 8 shards. The corpus size (601) and cell
count (12) are deliberately not divisible by the shard counts, so the
per-shard-equal padding paths (pad rows, pad cells, kernel over-fetch
slack) are all live.

The full matrix needs 8 simulated devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the
``tier1-multidevice`` CI job); in a single-device session the >1-shard
cases skip and the 1-shard mesh still exercises the whole shard_map path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MPADConfig
from repro.parallel.context import mesh_context
from repro.parallel.engine import shard_engine
from repro.search import (SearchEngine, ServeConfig, search_fn,
                          sharded_search_fn)

pytestmark = pytest.mark.multidevice

N, DIM, K = 601, 32, 10


def _data(seed=0, n=N, d=DIM):
    key = jax.random.key(seed)
    centers = jax.random.normal(key, (12, d)) * 2
    lab = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, 12)
    return centers[lab] + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 2), (n, d))


def _queries(nq=24):
    x = _data()
    return x[:nq] + 0.02 * jax.random.normal(jax.random.key(9), (nq, DIM))


_ENGINES = {}


def _config(index, **kw):
    """Per-kind config: stage knobs only where the pipeline has the stage
    (dead knobs raise at config time)."""
    base = dict(target_dim=8, rerank=64, index=index,
                mpad=MPADConfig(m=8, iters=16), fit_sample=512)
    if index in ("ivf", "ivfpq"):
        base.update(nlist=12, nprobe=5)
    if index in ("pq", "ivfpq"):
        base.update(pq_subspaces=8, pq_centroids=64)
    base.update(kw)
    return ServeConfig(**base)


def _engine(index):
    """One build per index kind (MPAD fit + index train are the slow part)."""
    if index not in _ENGINES:
        _ENGINES[index] = SearchEngine(_data(), _config(index))
    return _ENGINES[index]


def _mesh(shards):
    if jax.device_count() < shards:
        pytest.skip(f"needs {shards} devices (run under XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={shards})")
    return jax.make_mesh((shards,), ("data",),
                         devices=jax.devices()[:shards])


def _assert_parity(eng, kw, shards, q=None, k=K):
    q = _queries() if q is None else q
    mesh = _mesh(shards)
    d1, i1 = search_fn(eng.state, q, k, **kw)
    sstate = shard_engine(eng.state, mesh)
    d2, i2 = sharded_search_fn(sstate, q, k, mesh=mesh, axis="data", **kw)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-5)


# --- the acceptance matrix ---------------------------------------------------

@pytest.mark.parametrize("shards", (1, 2, 8))
@pytest.mark.parametrize("lut", ("f32", "int8"))
@pytest.mark.parametrize("index", ("flat", "ivf", "pq", "ivfpq"))
def test_sharded_matches_single_device(index, lut, shards):
    eng = _engine(index)
    coded = index in ("pq", "ivfpq")
    kw = dict(nprobe=5, rerank=64, backend="jnp",
              lut_dtype=lut if coded else "f32")
    _assert_parity(eng, kw, shards)


@pytest.mark.parametrize("lut", ("f32", "int8"))
@pytest.mark.parametrize("index", ("pq", "ivfpq"))
def test_sharded_kernel_backend_parity(index, lut):
    """The fused Pallas scans run inside shard_map too; the shared-codes
    entry exercises the over-fetch slack that keeps shard-pad rows from
    displacing real candidates."""
    shards = min(2, jax.device_count())
    eng = _engine(index)
    kw = dict(nprobe=5, rerank=64, backend="kernel", lut_dtype=lut)
    _assert_parity(eng, kw, shards)


# --- engine-level routing ----------------------------------------------------

def test_engine_shard_roundtrip_and_context_mesh():
    """``SearchEngine.shard()`` (mesh from the context) must not change
    what ``search`` returns, and must key its own compile cache."""
    eng = _engine("ivfpq")
    q = _queries()
    d0, i0 = eng.search(q, K)
    mesh = _mesh(min(2, jax.device_count()))
    with mesh_context(mesh):
        eng.shard()
    try:
        assert eng.sharded_state is not None
        d1, i1 = eng.search(q, K)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), atol=1e-5)
        assert eng.compile_count >= 2     # single-device + sharded programs
    finally:
        eng.sharded_state = None          # _ENGINES is shared across tests


def test_shard_engine_requires_mesh():
    eng = _engine("flat")
    with pytest.raises(RuntimeError, match="mesh"):
        shard_engine(eng.state)


def test_sharded_state_padding_is_per_shard_equal():
    shards = min(8, jax.device_count())
    mesh = _mesh(shards)
    sstate = shard_engine(_engine("ivfpq").state, mesh)
    assert sstate.index.kind == "ivfpq"
    ix = sstate.index.payload                        # ShardedIVFPQ
    assert sstate.corpus.shape[0] % shards == 0
    assert ix.lists.shape[0] % shards == 0
    assert ix.codes_cell.shape[:2] == ix.lists.shape
    assert int(sstate.n_real) == N
    # pad cells are empty posting rows
    nlist_real = ix.centroids.shape[0]
    pads = np.asarray(ix.lists)[nlist_real:]
    assert (pads == -1).all()


def test_shard_aware_builders_prepad_cells():
    """``build_ivf/build_ivfpq(shards=)`` emit per-shard-equal cell layouts
    up front; ``shard_engine``'s padding is then a no-op on them, and scan
    results are unchanged vs the unsharded build."""
    from repro.search import build_ivf, build_ivfpq, ivf_search
    from repro.search.ivfpq import ivfpq_search
    x = _data()
    key = jax.random.key(1)
    plain = build_ivf(key, x, nlist=12)
    pre = build_ivf(key, x, nlist=12, shards=8)
    assert plain.lists.shape[0] == 12
    assert pre.lists.shape[0] == 16 and pre.lists.shape[0] % 8 == 0
    assert (np.asarray(pre.lists)[12:] == -1).all()      # pad cells empty
    q = _queries()
    _, i1 = ivf_search(plain, q, K, nprobe=5)
    _, i2 = ivf_search(pre, q, K, nprobe=5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    plain = build_ivfpq(key, x, nlist=12, m_subspaces=8, n_centroids=64)
    pre = build_ivfpq(key, x, nlist=12, m_subspaces=8, n_centroids=64,
                      shards=8)
    assert pre.codes_cell.shape[0] == 16 == pre.bias_cell.shape[0]
    _, i1 = ivfpq_search(plain, q, K, nprobe=5)
    _, i2 = ivfpq_search(pre, q, K, nprobe=5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_shard_donate_releases_dense_buffers():
    """``shard(donate=True)`` frees the dense EngineState (no 2x database
    memory): every dense leaf is deleted or — by identity — lives on in
    the sharded pytree; re-sharding raises; results are unchanged."""
    shards = min(2, jax.device_count())
    x = _data()
    eng = SearchEngine(x, _config("ivfpq"))
    q = _queries()
    d0, i0 = eng.search(q, K)
    old_leaves = jax.tree.leaves(eng.state)
    eng.shard(_mesh(shards), donate=True)
    placed = {id(leaf) for leaf in jax.tree.leaves(eng.sharded_state)}
    for leaf in old_leaves:
        # the caller-supplied corpus array stays caller-owned by design
        assert leaf.is_deleted() or id(leaf) in placed or leaf is x
    assert eng.state is None
    with pytest.raises(RuntimeError, match="donate"):
        eng.shard(_mesh(shards))                 # no dense state to re-shard
    d1, i1 = eng.search(q, K)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), atol=1e-5)
    # the public reducer was re-pointed at the replicated projection
    # copies, so it keeps working after the dense arrays were donated
    red = eng.reducer(q)
    assert red.shape == (q.shape[0], 8)


def test_shard_donate_spares_user_owned_corpus():
    """A caller-supplied f32 corpus array passes into EngineState by
    reference; donation must not delete it out from under the caller."""
    x = jnp.asarray(_data(), jnp.float32)
    eng = SearchEngine(x, ServeConfig(target_dim=None, index="flat"))
    eng.shard(_mesh(1), donate=True)
    assert not x.is_deleted()
    assert float(jnp.sum(x)) == float(jnp.sum(x))    # still usable


def test_balanced_cell_placement_improves_shard_mass():
    """Load-aware placement (greedy bin-pack by posting mass) must beat
    the unbalanced layout on a skewed corpus, without changing results."""
    from repro.search import balance_cells, build_ivfpq
    from repro.search.ivfpq import ivfpq_search
    key = jax.random.key(0)
    nlist, shards, d = 16, 4, DIM
    sizes = [600, 300, 150, 80, 40, 30, 20, 15] + [10] * 8
    centers = jax.random.normal(key, (16, d)) * 6
    x = jnp.concatenate([
        centers[i] + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                             (s, d))
        for i, s in enumerate(sizes)])

    def imbalance(lists):
        per = lists.shape[0] // shards
        mass = [(np.asarray(lists[s * per:(s + 1) * per]) >= 0).sum()
                for s in range(shards)]
        return max(mass) - min(mass)

    bal = build_ivfpq(jax.random.key(1), x, nlist, 8, 64, shards=shards)
    raw = build_ivfpq(jax.random.key(1), x, nlist, 8, 64, shards=shards,
                      balance=False)
    assert imbalance(bal.lists) < imbalance(raw.lists)
    q = x[:32] + 0.02 * jax.random.normal(jax.random.key(9), (32, d))
    _, i1 = ivfpq_search(bal, q, K, nprobe=8)
    _, i2 = ivfpq_search(raw, q, K, nprobe=8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    # the permutation is a permutation: every cell placed exactly once
    counts = np.asarray(jnp.bincount(
        jnp.argmin(((x[:, None, :] - bal.centroids[None]) ** 2).sum(-1),
                   axis=1), length=nlist))
    perm = balance_cells(counts, shards)
    assert sorted(perm.tolist()) == list(range(nlist))


def test_sharded_bucket_padding_never_perturbs_results():
    """Query-bucket pad rows must stay row-independent through the
    all_gather + pmin merge, exactly as on the single-device path."""
    eng = _engine("ivf")
    mesh = _mesh(min(2, jax.device_count()))
    eng.shard(mesh)
    try:
        q = _queries(24)
        d24, i24 = eng.search(q, K)         # bucket 64 (padded)
        d5, i5 = eng.search(q[:5], K)       # bucket 8 (small-batch path)
        np.testing.assert_array_equal(np.asarray(i24)[:5], np.asarray(i5))
        np.testing.assert_allclose(np.asarray(d24)[:5], np.asarray(d5),
                                   atol=1e-5)
    finally:
        eng.sharded_state = None
