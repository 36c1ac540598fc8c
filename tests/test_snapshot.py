"""Engine snapshot persistence: save/load parity for every index kind and
LUT dtype, streaming snapshots taken mid-delta, restore onto a mesh, and
the no-new-recompiles pin on restored engines."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MPADConfig
from repro.search import StreamConfig, build_engine, load_engine

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


_SPECS = [
    "flat",
    "qpad8>rr64",
    "ivf12x5",
    "pq8x64",
    "pq8x64:i8",
    "qpad8>ivf12x5",
    "ivf12x5>pq8x64",
    "ivf12x5>pq8x64:i8",
    "qpad8>ivf12x5>pq8x64:i8",
]


def _engine(spec, **runtime):
    runtime.setdefault("fit_sample", 512)
    runtime.setdefault("mpad", MPADConfig(m=8, iters=16))
    return build_engine(_data(), spec, **runtime)


# --- save/load parity: all 4 kinds x f32/int8 LUTs ---------------------------

@pytest.mark.parametrize("spec", _SPECS)
def test_save_load_search_parity(spec, tmp_path):
    """load_engine(save(e)).search == e.search, pinned exactly."""
    eng = _engine(spec)
    q = _queries()
    d1, i1 = eng.search(q, K)
    eng.save(str(tmp_path))
    eng2 = load_engine(str(tmp_path))
    assert eng2.spec == eng.spec
    d2, i2 = eng2.search(q, K)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-6)


def test_restored_engine_compiles_no_new_program_shapes(tmp_path):
    """The restored engine reproduces shapes, dtypes, and the index kind's
    treedef exactly, so it holds ONE compiled program per (knobs, k,
    bucket) — same as a fresh build; repeated searches add nothing."""
    eng = _engine("qpad8>ivf12x5>pq8x64:i8")
    q = _queries()
    _, i1 = eng.search(q, K)
    assert eng.compile_count == 1
    eng.save(str(tmp_path))
    eng2 = load_engine(str(tmp_path))
    for _ in range(3):
        _, i2 = eng2.search(q, K)
    assert eng2.compile_count == 1, eng2.compile_count
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_runtime_overrides_on_load(tmp_path):
    eng = _engine("ivf12x5")
    eng.save(str(tmp_path))
    eng2 = load_engine(str(tmp_path), query_bucket=16)
    assert eng2.config.query_bucket == 16
    eng2.search(_queries(3), K)
    assert eng2.last_bucket == 4            # small-batch path intact


# --- streaming snapshots -----------------------------------------------------

@pytest.mark.stream
@pytest.mark.parametrize("spec", ["qpad8>rr128", "ivf12x5>pq8x64:i8>rr128"])
def test_streaming_snapshot_mid_delta(spec, tmp_path):
    """A snapshot taken mid-delta (un-compacted upserts + tombstones in
    flight) restores mid-delta: same results, same delta fill, and the
    write path keeps working — compaction after restore equals compaction
    without the round trip."""
    rng = np.random.RandomState(0)
    vecs = rng.randn(24, DIM).astype(np.float32)

    eng = _engine(spec, stream=StreamConfig(delta_capacity=64))
    eng.upsert(np.arange(N, N + 24), vecs)          # fresh delta rows
    eng.delete(np.arange(0, 30, 3))                 # base tombstones
    eng.upsert(np.array([5, 8]),
               rng.randn(2, DIM).astype(np.float32))   # base overwrites
    q = _queries()
    d1, i1 = eng.search(q, K)
    assert int(eng.store.delta_count) > 0          # genuinely mid-delta
    eng.save(str(tmp_path))
    eng2 = load_engine(str(tmp_path))
    assert int(eng2.store.delta_count) == int(eng.store.delta_count)
    assert eng2._delta_used == int(eng.store.delta_count)
    d2, i2 = eng2.search(q, K)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-6)
    # the write lifecycle continues from the snapshot
    for e in (eng, eng2):
        e.upsert(np.arange(N + 100, N + 110),
                 rng.randn(10, DIM).astype(np.float32))
        e.compact()
    _, i1c = eng.search(q, K)
    _, i2c = eng2.search(q, K)
    np.testing.assert_array_equal(np.asarray(i1c), np.asarray(i2c))


# --- restore onto a mesh -----------------------------------------------------

@pytest.mark.multidevice
def test_load_engine_onto_mesh(tmp_path):
    """``load_engine(dir, mesh=...)`` places the snapshot through
    ``restore_resharded`` and partitions it — identical ids to the
    single-device restore, no dense 2x left behind."""
    shards = min(2, jax.device_count())
    mesh = jax.make_mesh((shards,), ("data",),
                         devices=jax.devices()[:shards])
    eng = _engine("qpad8>ivf12x5>pq8x64")
    q = _queries()
    d1, i1 = eng.search(q, K)
    eng.save(str(tmp_path))
    eng2 = load_engine(str(tmp_path), mesh=mesh)
    assert eng2.sharded_state is not None
    assert eng2.state is None                      # dense copy donated
    d2, i2 = eng2.search(q, K)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-5)


# --- guard rails -------------------------------------------------------------

def test_save_after_donate_raises(tmp_path):
    mesh = jax.make_mesh((1,), ("data",))
    eng = _engine("ivf12x5")
    eng.shard(mesh, donate=True)
    with pytest.raises(RuntimeError, match="donate"):
        eng.save(str(tmp_path))


@pytest.mark.stream
def test_load_rejects_stream_override(tmp_path):
    """StreamConfig capacities are baked into the saved store's shapes —
    overriding stream= at load is refused instead of mis-provisioning."""
    eng = _engine("flat", stream=StreamConfig(delta_capacity=64))
    eng.save(str(tmp_path))
    with pytest.raises(ValueError, match="stream"):
        load_engine(str(tmp_path), stream=StreamConfig(delta_capacity=8))
    assert load_engine(str(tmp_path)).config.stream.delta_capacity == 64


def test_load_missing_snapshot_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="engine.json"):
        load_engine(str(tmp_path))


def test_snapshot_restores_reducer(tmp_path):
    eng = _engine("qpad8")
    eng.save(str(tmp_path))
    eng2 = load_engine(str(tmp_path))
    q = _queries(4)
    np.testing.assert_allclose(np.asarray(eng.reducer(q)),
                               np.asarray(eng2.reducer(q)), atol=1e-6)


def test_flat_alias_not_saved_twice(tmp_path):
    """flat with no Reduce stage scans the corpus itself: the snapshot
    stores the rows once and restore re-aliases the payload."""
    eng = build_engine(_data(), "flat")
    eng.save(str(tmp_path))
    eng2 = load_engine(str(tmp_path))
    assert eng2.state.index.payload is eng2.state.corpus
    q = _queries()
    np.testing.assert_array_equal(np.asarray(eng.search(q, K)[1]),
                                  np.asarray(eng2.search(q, K)[1]))


def test_load_ignores_retired_pq_interpret_key(tmp_path):
    """Snapshots written while ``ServeConfig.pq_interpret`` existed carry
    the key in ``engine.json``; they still load (the platform now picks
    Pallas interpret mode) and serve the same ids."""
    import json
    import os
    eng = _engine("ivf12x5>pq8x64@kernel")
    eng.save(str(tmp_path))
    meta_path = os.path.join(str(tmp_path), "engine.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["runtime"]["pq_interpret"] = True
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    eng2 = load_engine(str(tmp_path))
    assert not hasattr(eng2.config, "pq_interpret")
    q = _queries()
    np.testing.assert_array_equal(np.asarray(eng.search(q, K)[1]),
                                  np.asarray(eng2.search(q, K)[1]))
