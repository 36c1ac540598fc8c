"""Durability subsystem: WAL, crash recovery, background compaction, policy.

The contracts pinned here:

* **WAL framing** — records round-trip byte-exact across segment
  rotation; a torn tail (the crash artifact) is skipped by readers and
  truncated by a resuming writer; damage anywhere *else* raises
  ``WalError``; truncation after a durable snapshot unlinks only fully
  covered segments.
* **crash recovery** — killing the engine at EVERY WAL record boundary
  (and mid-compaction-swap, via ``runtime.fault.FailureInjector`` on the
  named ``crash_hook`` points) then ``load_engine`` lands on search ids
  identical to an uncrashed oracle that ran the same op prefix — for
  flat / ivf / pq / ivfpq — and the fully recovered store matches the
  from-scratch ``rebuild_state`` oracle.
* **non-blocking compaction** — searches concurrent with a background
  fold return ids identical to the pre- OR post-compaction store, never
  a mix, on 1/2/8 (simulated) devices; writes during the fold survive
  the swap.
* **maintenance policy** — tombstone density triggers vacuum from
  ``delete``, headroom pressure triggers proactive grow, encode-error
  drift above the LUT noise floor advises (or runs) a quantizer rebuild;
  decisions are WAL records and replay deterministically.
"""
import os
import shutil
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MPADConfig
from repro.runtime.fault import FailureInjector
from repro.search import (DurabilityConfig, PolicyConfig, SearchEngine,
                          ServeConfig, StreamConfig, Wal, WalError,
                          load_engine, rebuild_state, search_fn)
from repro.search.durability.wal import (RT_COMPACT, RT_DELETE, RT_POLICY,
                                         RT_UPSERT, decode_delete,
                                         decode_upsert, encode_delete,
                                         encode_policy, encode_upsert,
                                         decode_policy, iter_records,
                                         wal_tail_seq)

pytestmark = pytest.mark.durability

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


def _cfg(index, target_dim=None, **stream_kw):
    stream_kw.setdefault("delta_capacity", 64)
    kw = dict(target_dim=target_dim, rerank=128, index=index,
              mpad=MPADConfig(m=8, iters=16) if target_dim else None,
              fit_sample=512, stream=StreamConfig(**stream_kw))
    if index in ("ivf", "ivfpq"):
        kw.update(nlist=12, nprobe=12)
    if index in ("pq", "ivfpq"):
        kw.update(pq_subspaces=8, pq_centroids=64)
    return ServeConfig(**kw)


def _rows(seed, n):
    return np.asarray(_data(seed=seed, n=n), np.float32)


# --- WAL unit layer ----------------------------------------------------------

def test_wal_roundtrip_and_rotation(tmp_path):
    """Records come back in order, byte-exact, across forced segment
    rotation; truncation after a snapshot unlinks only covered segments."""
    d = str(tmp_path / "wal")
    wal = Wal(d, DurabilityConfig(fsync="never", segment_bytes=256))
    payloads = []
    for i in range(30):
        p = encode_upsert(np.arange(i + 1, dtype=np.int32),
                          np.full((i + 1, 4), float(i), np.float32))
        payloads.append((RT_UPSERT, p))
        wal.append(RT_UPSERT, p)
    wal.append(RT_COMPACT, b"")
    payloads.append((RT_COMPACT, b""))
    wal.close()
    got = list(iter_records(d))
    assert [seq for seq, _, _ in got] == list(range(31))
    assert [(rt, pl) for _, rt, pl in got] == payloads
    segs = [f for f in os.listdir(d) if f.endswith(".log")]
    assert len(segs) > 1, "256-byte segments must have rotated"
    assert wal_tail_seq(d) == 30
    # truncate: re-open resuming, drop everything before seq 20
    wal = Wal(d, DurabilityConfig(fsync="never", segment_bytes=256),
              resume=True)
    wal.truncate(20)
    remaining = list(iter_records(d))
    assert remaining[-1][0] == 30
    assert remaining[0][0] <= 21          # nothing past the snapshot lost
    assert len(os.listdir(d)) < len(segs) + 1
    wal.close()


def test_wal_truncate_respects_pinned_floor(tmp_path):
    """A pinned floor (the newest base snapshot's position) clamps
    truncation: records past it survive even when the caller asks for
    more — they are what re-seeds a base-seeded follower."""
    d = str(tmp_path / "wal")
    wal = Wal(d, DurabilityConfig(fsync="never", segment_bytes=128))
    for i in range(20):
        wal.append(RT_DELETE, encode_delete(np.arange(8)))
    assert wal.stats()["floor_seq"] == -1            # unpinned
    wal.pin_floor(5)
    wal.truncate(15)                                 # clamped to 5
    assert wal.stats()["floor_seq"] == 5
    wal.close()                                      # flush buffered tail
    remaining = [seq for seq, _, _ in iter_records(d)]
    assert set(range(6, 20)).issubset(remaining)     # floor tail intact


def test_wal_torn_tail_skipped_and_truncated_on_resume(tmp_path):
    """A half-written final frame (the crash artifact) is invisible to
    readers and removed by a resuming writer, which then continues the
    sequence."""
    d = str(tmp_path / "wal")
    wal = Wal(d, DurabilityConfig(fsync="never"))
    for i in range(5):
        wal.append(RT_DELETE, encode_delete(np.arange(i + 1)))
    wal.close()
    seg = sorted(os.listdir(d))[-1]
    path = os.path.join(d, seg)
    with open(path, "ab") as f:
        f.write(b"\x07\x07\x07")                     # torn tail
    assert wal_tail_seq(d) == 4                      # reader stops clean
    size_torn = os.path.getsize(path)
    wal = Wal(d, DurabilityConfig(fsync="never"), resume=True)
    assert os.path.getsize(path) == size_torn - 3    # tail truncated
    assert wal.append(RT_COMPACT) == 5               # sequence continues
    wal.close()
    assert wal_tail_seq(d) == 5


def test_wal_midlog_corruption_raises(tmp_path):
    """The same damage before the tail of the last segment is real
    corruption, not a torn tail: reading raises ``WalError``."""
    d = str(tmp_path / "wal")
    wal = Wal(d, DurabilityConfig(fsync="never", segment_bytes=128))
    for i in range(20):
        wal.append(RT_DELETE, encode_delete(np.arange(8)))
    wal.close()
    first = sorted(os.listdir(d))[0]                 # NOT the last segment
    path = os.path.join(d, first)
    data = bytearray(open(path, "rb").read())
    data[-1] ^= 0xFF                                 # flip a payload byte
    open(path, "wb").write(bytes(data))
    with pytest.raises(WalError):
        list(iter_records(d))


def test_wal_refuses_existing_history_without_resume(tmp_path):
    d = str(tmp_path / "wal")
    wal = Wal(d, DurabilityConfig(fsync="never"))
    wal.append(RT_COMPACT)
    wal.close()
    with pytest.raises(RuntimeError, match="load_engine"):
        Wal(d, DurabilityConfig(fsync="never"))


def test_payload_codecs_roundtrip():
    ids = np.asarray([3, -1, 7, 2**31 - 1], np.int32)
    vecs = np.arange(16, dtype=np.float32).reshape(4, 4)
    rid, rvec = decode_upsert(encode_upsert(ids, vecs))
    np.testing.assert_array_equal(rid, ids)
    np.testing.assert_array_equal(rvec, vecs)
    np.testing.assert_array_equal(decode_delete(encode_delete(ids)), ids)
    dec = {"decision": "grow", "row_extra": 256, "cell_extra": 64}
    assert decode_policy(encode_policy(dec)) == dec


# --- crash recovery at every record boundary ---------------------------------

# each op is sized under the delta compact point (48 of 64), so ops map
# 1:1 onto WAL records and an op prefix IS a record prefix
_OPS = [
    ("upsert", np.arange(600, 630, dtype=np.int32), 1),
    ("delete", np.asarray([3, 5, 600, 604], np.int32), None),
    ("upsert", np.arange(625, 640, dtype=np.int32), 2),
    ("compact", None, None),
    ("upsert", np.arange(640, 670, dtype=np.int32), 3),
    ("delete", np.asarray([10, 11, 650], np.int32), None),
    ("upsert", np.arange(7, 12, dtype=np.int32), 4),   # overwrite base rows
]


def _apply_ops(eng, ops):
    for op, ids, seed in ops:
        if op == "upsert":
            eng.upsert(ids, _rows(seed, len(ids)))
        elif op == "delete":
            eng.delete(ids)
        else:
            eng.compact()


def _tail_records(live):
    """The WAL records past the newest durable snapshot's mark — the
    replay script a recovery of ``live`` would run."""
    import json
    meta = json.load(open(os.path.join(live, "engine.json")))
    return (meta["wal_seq"],
            list(iter_records(os.path.join(live, "wal"),
                              after=meta["wal_seq"])))


def _prefix_dir(src, dst, records, p, mark_payload=b"-1"):
    """A copy of the durable directory as a crash at the boundary after
    tail record ``p`` would leave it: snapshot intact, WAL holding the
    snapshot mark (seq 0) + the first ``p`` tail records."""
    os.makedirs(dst)
    for f in os.listdir(src):
        if f != "wal":
            shutil.copy2(os.path.join(src, f), os.path.join(dst, f))
    wal = Wal(os.path.join(dst, "wal"), DurabilityConfig(fsync="never"))
    wal.append(4, mark_payload)                  # RT_SNAPSHOT mark, seq 0
    for _, rtype, payload in records[:p]:
        wal.append(rtype, payload)
    wal.close()


@pytest.mark.parametrize("index", ("flat", "ivf", "pq", "ivfpq"))
def test_recovery_at_every_record_boundary(index, tmp_path):
    """The acceptance property: a crash after any WAL record recovers to
    search ids identical to an uncrashed engine that ran exactly that
    prefix of operations."""
    q = _queries()
    cfg = _cfg(index)
    live = str(tmp_path / "live")
    eng = SearchEngine(_data(), cfg).durable(
        live, DurabilityConfig(fsync="batch"))
    _apply_ops(eng, _OPS)
    eng._wal.sync()
    _, records = _tail_records(live)
    assert len(records) == len(_OPS)         # 1:1 op <-> record mapping
    # the uncrashed oracle: same deterministic build, ops applied one at
    # a time, ids captured at every boundary
    oracle = SearchEngine(_data(), cfg)
    want = [np.asarray(oracle.search(q, K)[1])]
    for op in _OPS:
        _apply_ops(oracle, [op])
        want.append(np.asarray(oracle.search(q, K)[1]))
    for p in range(len(records) + 1):
        crash = str(tmp_path / f"crash{p}")
        _prefix_dir(live, crash, records, p)
        rec = load_engine(crash)
        assert rec._replayed == p
        got = np.asarray(rec.search(q, K)[1])
        np.testing.assert_array_equal(got, want[p], err_msg=f"prefix {p}")


def test_recovered_store_matches_rebuild_oracle(tmp_path):
    """After recovery + compact, the store serves exactly what a
    from-scratch rebuild over the surviving rows (same frozen
    quantizers) serves — recovery does not fork the streaming
    equivalence contract."""
    index = "ivfpq"
    live = str(tmp_path / "live")
    eng = SearchEngine(_data(), _cfg(index)).durable(
        live, DurabilityConfig(fsync="batch"))
    _apply_ops(eng, _OPS)
    rec = load_engine(live)
    rec.compact()
    alive = {}
    for i, v in enumerate(np.asarray(_data(), np.float32)):
        alive[i] = v
    for op, ids, seed in _OPS:
        if op == "upsert":
            for j, rid in enumerate(ids):
                alive[int(rid)] = _rows(seed, len(ids))[j]
        elif op == "delete":
            for rid in ids:
                alive.pop(int(rid), None)
    surv_ids = np.array(sorted(alive))
    surv = jnp.asarray(np.stack([alive[i] for i in surv_ids]))
    oracle = rebuild_state(rec.frozen, surv, index=index)
    q = _queries()
    d_r, i_r = search_fn(oracle, q, K, nprobe=12, rerank=128,
                         backend="jnp", lut_dtype="f32")
    d_s, i_s = rec.search(q, K)
    np.testing.assert_array_equal(np.sort(np.asarray(i_s), axis=1),
                                  np.sort(surv_ids[np.asarray(i_r)], axis=1))


def test_torn_tail_after_workload_recovers_to_last_record(tmp_path):
    """Garbage appended by a crash mid-append is dropped; recovery lands
    on the last intact record's state."""
    live = str(tmp_path / "live")
    cfg = _cfg("ivf")
    eng = SearchEngine(_data(), cfg).durable(
        live, DurabilityConfig(fsync="batch"))
    _apply_ops(eng, _OPS)
    q = _queries()
    want = np.asarray(eng.search(q, K)[1])
    wal_dir = os.path.join(live, "wal")
    seg = sorted(f for f in os.listdir(wal_dir) if f.endswith(".log"))[-1]
    with open(os.path.join(wal_dir, seg), "ab") as f:
        f.write(b"\x13\x37" * 9)                     # torn half-frame
    rec = load_engine(live)
    np.testing.assert_array_equal(np.asarray(rec.search(q, K)[1]), want)


@pytest.mark.parametrize("point,upto", [
    ("wal_appended", 1),     # crashed right after the first durable record
    ("compact_swap", 4),     # crashed mid-swap: barrier at op 4 replays
    ("vacuum", None),        # crashed entering vacuum (record is durable)
])
def test_injected_crash_at_lifecycle_points(point, upto, tmp_path):
    """``FailureInjector`` killing the engine at a named lifecycle point
    leaves a directory that recovers to the oracle state: everything
    WAL-logged before the kill replays (the log is ahead of the store,
    never behind)."""
    q = _queries()
    cfg = _cfg("ivf", policy=PolicyConfig(tombstone_density=0.2,
                                          tombstone_min_dead=32))
    live = str(tmp_path / "live")
    eng = SearchEngine(_data(), cfg).durable(
        live, DurabilityConfig(fsync="batch"))
    injector = FailureInjector(fail_at={point})
    eng.crash_hook = injector.maybe_fail
    ops = _OPS if point != "vacuum" else (
        _OPS + [("delete", np.arange(100, 300, dtype=np.int32), None)])
    with pytest.raises(RuntimeError, match="injected failure"):
        _apply_ops(eng, ops)
    # oracle: uncrashed engine running every op whose record is durable;
    # a compaction barrier / policy record replays to COMPLETION even
    # though the crash interrupted the action itself
    oracle = SearchEngine(_data(), cfg)
    n_durable = len(_tail_records(live)[1])
    applied = 0
    for op in ops:
        if applied >= n_durable:
            break
        _apply_ops(oracle, [op])
        applied += 1
    if upto is not None:
        assert n_durable == upto
    rec = load_engine(live)
    np.testing.assert_array_equal(np.asarray(rec.search(q, K)[1]),
                                  np.asarray(oracle.search(q, K)[1]))


def test_recovered_engine_resumes_the_log(tmp_path):
    """Recovery is not read-only: the recovered engine appends to the
    same WAL, and a second crash + recovery sees both histories."""
    live = str(tmp_path / "live")
    eng = SearchEngine(_data(), _cfg("flat")).durable(
        live, DurabilityConfig(fsync="batch"))
    eng.upsert(np.arange(600, 620, dtype=np.int32), _rows(1, 20))
    rec = load_engine(live)
    rec.upsert(np.arange(620, 640, dtype=np.int32), _rows(2, 20))
    rec.delete(np.asarray([600, 625], np.int32))
    q = _queries()
    want = np.asarray(rec.search(q, K)[1])
    rec2 = load_engine(live)
    # the tail now holds the pre-crash record plus the two the recovered
    # engine appended to the SAME log
    assert rec2._replayed == rec._replayed + 2
    np.testing.assert_array_equal(np.asarray(rec2.search(q, K)[1]), want)


def test_save_marks_and_truncates_the_wal(tmp_path):
    """A durable snapshot obsoletes the log prefix: save() records the
    covered seq, truncates covered segments, and the next recovery
    replays only the tail."""
    live = str(tmp_path / "live")
    eng = SearchEngine(_data(), _cfg("flat")).durable(
        live, DurabilityConfig(fsync="batch", segment_bytes=4096))
    for s in range(4):
        eng.upsert(np.arange(600 + 20 * s, 620 + 20 * s, dtype=np.int32),
                   _rows(s, 20))
    eng.save(live)                       # durable snapshot: log is prefix
    eng.upsert(np.arange(700, 710, dtype=np.int32), _rows(9, 10))
    q = _queries()
    want = np.asarray(eng.search(q, K)[1])
    rec = load_engine(live)
    # only the post-snapshot tail: the auto-compact barrier the last
    # upsert tripped (delta was 40/48 at the save) plus the upsert itself
    assert rec._replayed == 2
    np.testing.assert_array_equal(np.asarray(rec.search(q, K)[1]), want)


def test_snapshot_steps_increment_and_meta_names_checkpoint(tmp_path):
    """Each save lands under a fresh checkpoint step and the metadata
    names its checkpoint — a stray newer array file without a committed
    metadata (crash mid-save) is ignored at load."""
    import json
    live = str(tmp_path / "live")
    eng = SearchEngine(_data(), _cfg("flat")).durable(
        live, DurabilityConfig(fsync="batch"))
    eng.upsert(np.arange(600, 610, dtype=np.int32), _rows(1, 10))
    eng.save(live)
    meta = json.load(open(os.path.join(live, "engine.json")))
    named = meta["ckpt"]
    assert named in os.listdir(live)
    q = _queries()
    want = np.asarray(eng.search(q, K)[1])
    # simulate a crash between the array write and the metadata commit:
    # a newer checkpoint file exists but engine.json still names `named`
    stray = os.path.join(live, "ckpt_0000009999.npz")
    shutil.copy2(os.path.join(live, named), stray)
    with open(stray, "ab") as f:
        f.write(b"\x00")                 # would fail to parse if read
    rec = load_engine(live)
    np.testing.assert_array_equal(np.asarray(rec.search(q, K)[1]), want)


def test_durable_twice_raises(tmp_path):
    eng = SearchEngine(_data(), _cfg("flat")).durable(str(tmp_path / "d"))
    with pytest.raises(RuntimeError, match="already durable"):
        eng.durable(str(tmp_path / "d2"))


# --- non-blocking compaction -------------------------------------------------

def _bg_engine(index="ivf", **stream_kw):
    stream_kw.setdefault("background_compact", True)
    return SearchEngine(_data(), _cfg(index, **stream_kw))


def test_background_compaction_atomic_swap():
    """While the fold runs on the worker, searches serve the OLD store;
    after the swap they serve the NEW one — never a mix, and writes that
    landed during the fold survive it."""
    eng = _bg_engine()
    gate = threading.Event()
    eng.crash_hook = lambda p: gate.wait(30) if p == "compact_task" else None
    q = _queries()
    eng.upsert(np.arange(600, 640, dtype=np.int32), _rows(1, 40))
    pre = np.asarray(eng.search(q, K)[1])
    eng.upsert(np.arange(640, 660, dtype=np.int32), _rows(2, 20))
    assert eng.metrics().compact.pending
    for _ in range(4):
        mid = np.asarray(eng.search(q, K)[1])    # old store, mid-fold
        np.testing.assert_array_equal(mid, pre)
    # a write during the fold: lands live now, replayed onto the swap
    eng.delete(np.asarray([600], np.int32))
    during = np.asarray(eng.search(q, K)[1])
    assert 600 not in during
    gate.set()
    eng.finish_compact()
    m = eng.metrics()
    assert m.compact.swaps == 1
    assert not m.compact.pending
    post = np.asarray(eng.search(q, K)[1])
    assert 600 not in post
    # post-swap store == blocking-compaction oracle over the same ops
    oracle = _bg_engine(background_compact=False)
    oracle.upsert(np.arange(600, 640, dtype=np.int32), _rows(1, 40))
    oracle.upsert(np.arange(640, 660, dtype=np.int32), _rows(2, 20))
    oracle.delete(np.asarray([600], np.int32))
    oracle.compact()
    np.testing.assert_array_equal(post, np.asarray(oracle.search(q, K)[1]))


def test_background_compaction_poll_swaps_without_explicit_finish():
    """Once the fold completes, the next search entry installs it — no
    explicit finish_compact needed."""
    eng = _bg_engine()
    eng.upsert(np.arange(600, 640, dtype=np.int32), _rows(1, 40))
    eng.upsert(np.arange(640, 660, dtype=np.int32), _rows(2, 20))
    fut = eng._compact_future
    assert fut is not None
    fut.result()                          # wait for the fold (test only)
    eng.search(_queries(), K)             # poll point
    assert eng._compact_future is None
    assert eng.metrics().compact.swaps == 1


def test_background_overflow_falls_back_to_blocking():
    """A chunk that cannot fit the delta alongside the live rows forces
    the blocking path (never silently dropped rows)."""
    eng = _bg_engine()
    eng.upsert(np.arange(600, 640, dtype=np.int32), _rows(1, 40))
    eng.upsert(np.arange(640, 680, dtype=np.int32), _rows(2, 40))
    m = eng.metrics()
    assert not m.compact.pending
    assert m.compact.compactions >= 1
    ids = np.asarray(eng.search(_queries(), 5)[1])
    assert ids.shape == (16, 5)


@pytest.mark.multidevice
@pytest.mark.parametrize("shards", (1, 2, 8))
def test_background_compaction_atomic_on_shards(shards):
    """The acceptance property on a mesh: searches concurrent with the
    background fold return pre- OR post-compaction ids on every shard
    count — the re-shard happens inside the swap."""
    if jax.device_count() < shards:
        pytest.skip(f"needs {shards} devices (run under XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={shards})")
    mesh = jax.make_mesh((shards,), ("data",))
    eng = _bg_engine()
    eng.shard(mesh)
    gate = threading.Event()
    eng.crash_hook = lambda p: gate.wait(30) if p == "compact_task" else None
    q = _queries()
    eng.upsert(np.arange(600, 640, dtype=np.int32), _rows(1, 40))
    pre = np.asarray(eng.search(q, K)[1])
    eng.upsert(np.arange(640, 660, dtype=np.int32), _rows(2, 20))
    assert eng.metrics().compact.pending
    mid = np.asarray(eng.search(q, K)[1])
    np.testing.assert_array_equal(mid, pre)       # old store, whole fleet
    gate.set()
    eng.finish_compact()
    post = np.asarray(eng.search(q, K)[1])
    # single-device blocking oracle: the sharded swap must be invisible
    oracle = _bg_engine(background_compact=False)
    oracle.upsert(np.arange(600, 640, dtype=np.int32), _rows(1, 40))
    oracle.upsert(np.arange(640, 660, dtype=np.int32), _rows(2, 20))
    oracle.compact()
    np.testing.assert_array_equal(post, np.asarray(oracle.search(q, K)[1]))


# --- maintenance policy ------------------------------------------------------

def test_delete_triggers_vacuum_through_policy():
    """The delete-path fix: enough tombstones now routes into vacuum —
    dead rows are reclaimed, live ids survive, searches never return the
    deleted."""
    eng = SearchEngine(_data(), _cfg(
        "ivf", policy=PolicyConfig(tombstone_density=0.2,
                                   tombstone_min_dead=32)))
    q = _queries()
    keep = np.asarray(eng.search(q, K)[1])
    eng.delete(np.arange(200, 500, dtype=np.int32))
    m = eng.metrics()
    assert m.compact.vacuums == 1
    assert m.stream.tombstones == 0               # reclaimed, not masked
    assert m.stream.rows == N - 300
    got = np.asarray(eng.search(q, K)[1])
    assert not np.any((got >= 200) & (got < 500))


def test_delete_without_policy_never_vacuums():
    """No configured policy -> deletes only tombstone (the pre-existing
    contract, incl. the pinned no-recompile behavior, is untouched)."""
    eng = SearchEngine(_data(), _cfg("ivf"))
    eng.delete(np.arange(0, 400, dtype=np.int32))
    m = eng.metrics()
    assert m.compact.vacuums == 0
    assert m.stream.tombstones == 400


def test_policy_grow_headroom(tmp_path):
    """Capacity pressure: when post-compaction free rows drop under the
    headroom, the policy grows proactively — and the grow replays from
    the WAL as a policy record, not a re-derivation."""
    cfg = _cfg("flat", policy=PolicyConfig(grow_headroom=2.0))
    live = str(tmp_path / "live")
    eng = SearchEngine(_data(), cfg).durable(
        live, DurabilityConfig(fsync="batch"))
    cap0 = eng.metrics().stream.row_capacity
    ids = np.arange(600, 600 + 3 * 48, dtype=np.int32)
    eng.upsert(ids, _rows(5, len(ids)))           # forces compactions
    eng.compact()
    m = eng.metrics()
    assert m.compact.policy_grows >= 1
    assert m.stream.row_capacity > cap0
    wal_types = [rt for _, rt, _ in
                 iter_records(os.path.join(live, "wal"))]
    assert RT_POLICY in wal_types
    q = _queries()
    rec = load_engine(live)
    assert rec.metrics().stream.row_capacity == m.stream.row_capacity
    np.testing.assert_array_equal(np.asarray(rec.search(q, K)[1]),
                                  np.asarray(eng.search(q, K)[1]))


def test_drift_advises_then_auto_rebuilds():
    """Shifted data drives the encode error over the baseline ratio:
    default policy surfaces "advise_rebuild" in stats; auto_rebuild=True
    runs the retrain and re-bases the drift reference."""
    mk = lambda auto: SearchEngine(_data(), _cfg(
        "pq", policy=PolicyConfig(drift_ratio=2.0, drift_min_rows=32,
                                  auto_rebuild=auto)))
    shifted = np.asarray(_data(seed=4), np.float32)[:48] * 6 + 30
    adv = mk(False)
    adv.upsert(np.arange(600, 648, dtype=np.int32), shifted)
    adv.compact()
    m = adv.metrics()
    assert m.policy.decisions.get("advise_rebuild", 0) >= 1
    assert m.compact.rebuilds == 0
    assert m.policy.drift_ratio > 2.0
    auto = mk(True)
    auto.upsert(np.arange(600, 648, dtype=np.int32), shifted)
    auto.compact()
    m = auto.metrics()
    assert m.compact.rebuilds == 1
    assert m.policy.observed_rows == 0            # re-based after retrain
    # the retrained engine still serves every live id
    got = np.asarray(auto.search(_queries(), K)[1])
    assert got.min() >= 0


def test_rebuild_replays_deterministically(tmp_path):
    """A WAL-logged rebuild carries its seed: recovery reruns the exact
    same retrain and lands on identical search ids."""
    cfg = _cfg("pq", policy=PolicyConfig(drift_ratio=2.0, drift_min_rows=32,
                                         auto_rebuild=True))
    live = str(tmp_path / "live")
    eng = SearchEngine(_data(), cfg).durable(
        live, DurabilityConfig(fsync="batch"))
    shifted = np.asarray(_data(seed=4), np.float32)[:48] * 6 + 30
    eng.upsert(np.arange(600, 648, dtype=np.int32), shifted)
    eng.compact()                                  # drift -> logged rebuild
    assert eng.metrics().compact.rebuilds == 1
    q = _queries()
    rec = load_engine(live)
    assert rec.metrics().compact.rebuilds == 1
    np.testing.assert_array_equal(np.asarray(rec.search(q, K)[1]),
                                  np.asarray(eng.search(q, K)[1]))


def test_metrics_surface():
    """The public counters window: benches and tests read the typed
    metrics() tree, not private fields (stats() is gone)."""
    eng = SearchEngine(_data(), _cfg("ivfpq"))
    eng.upsert(np.arange(600, 620, dtype=np.int32), _rows(1, 20))
    m = eng.metrics()
    assert m.engine.streaming and not m.engine.sharded
    assert m.stream.delta_used == 20
    assert m.stream.rows == N
    for name in ("compactions", "swaps", "vacuums", "rebuilds",
                 "policy_grows"):
        assert getattr(m.compact, name) >= 0
    assert m.wal is None                          # not durable
    assert not hasattr(eng, "stats")              # removed in favor of metrics
    ro = SearchEngine(_data(), ServeConfig(index="flat"))
    assert not ro.metrics().engine.streaming
