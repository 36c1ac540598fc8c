"""Engine snapshot persistence: spec + arrays, restore anywhere.

``save_engine`` writes a serving engine into a directory as two pieces:

* ``engine.json`` — the pipeline **spec string** (the grammar of
  ``repro.search.spec``), the runtime knobs, and the streaming config;
  everything needed to rebuild the engine *shape* without the corpus.
* ``ckpt_*.npz`` — every array leaf, flattened by pytree key path through
  ``repro.runtime.checkpoint`` (atomic write + retention). Read-only
  engines persist their ``EngineState``; streaming engines persist the
  ``StreamStore`` + ``FrozenParams`` pair — the delta segment, tombstone
  bitmap, and id maps included, so a snapshot taken **mid-delta**
  restores mid-delta (un-compacted writes survive the round trip).

``load_engine`` rebuilds the ``SearchEngine`` around the restored arrays
— no MPAD refit, no index retrain, and (because shapes, dtypes, and the
index kind's pytree structure are reproduced exactly) **no new program
shapes**: the restored engine compiles the same one program per
(knobs, k, bucket) a fresh build would. Pass ``mesh=`` to restore onto a
device mesh: the dense leaves are placed through
``repro.runtime.checkpoint.restore_resharded`` (checkpoints are
shard-agnostic npz files — the elastic-scaling primitive) and the engine
is then partitioned with the usual layout pass (``shard``; read-only
restores donate the transient dense copy, so there is no standing 2x).

On a **durable** engine (``engine.durable(dir)``) the snapshot directory
also holds the write-ahead log: ``save_engine`` commits crash-consistently
(fresh checkpoint step -> fsync'd metadata replace -> WAL snapshot-mark +
truncate) and ``load_engine`` replays the log tail on top of the restored
store, so recovery lands on the exact pre-crash state (see
``repro.search.durability``).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.runtime.checkpoint import (checkpoint_step, latest_checkpoint,
                                      restore_checkpoint, restore_resharded,
                                      save_arrays, save_checkpoint)
from .durability.policy import PolicyConfig
from .durability.wal import RT_SNAPSHOT, DurabilityConfig, Wal
from .reducers import Reducer, get_reducer_ops
from .registry import Index, get_ops
from .segments import FrozenParams, StreamConfig, StreamStore
from .serve import EngineState, SearchEngine, config_from_spec
from .spec import format_spec, parse_spec

__all__ = ["save_engine", "load_engine", "SNAPSHOT_META"]

SNAPSHOT_META = "engine.json"
_SCHEMA = "qpad.engine_snapshot.v1"
# engine knobs a pipeline spec does not carry; persisted verbatim
_RUNTIME_FIELDS = ("query_bucket", "small_batch", "compact_batch",
                   "prefilter_batch", "fit_sample", "seed")
# knobs older snapshots persisted that no longer exist; ignored at load
# (pq_interpret: the platform now picks Pallas interpret mode)
_RETIRED_RUNTIME_FIELDS = ("pq_interpret",)


class _Leaf:
    """Placeholder leaf in a shape-free skeleton pytree (filled from the
    checkpoint by key path)."""

    def __repr__(self):
        return "<leaf>"


_L = _Leaf()


# StreamStore fields that are optional per index kind / projection; which
# ones a snapshot carries is recorded in its meta at save time
_OPT_STORE_FIELDS = ("reduced", "codes", "bias", "lists", "codes_cell",
                     "bias_cell", "delta_reduced")

# StreamStore fields a pure delta write path mutates — everything an
# INCREMENTAL snapshot must carry. The base arrays (corpus, codes,
# lists, ... and the frozen quantizers) only change at compaction /
# vacuum / rebuild / grow, which dirties the base and forces the next
# snapshot to be full.
_INC_STORE_FIELDS = ("row_ids", "n_rows", "dead", "delta_vectors",
                     "delta_ids", "delta_count", "delta_reduced")


def _snapshot_skeleton(kind: str, reducer: Optional[str], streaming: bool,
                       flat_alias: bool, store_fields=()):
    """The snapshot pytree with placeholder leaves — the structure comes
    from the spec metadata (kind, reducer kind, streaming, the optional
    store fields present at save time) plus the ops registries' per-kind
    shapes (``ReducerOps.skeleton``,
    ``payload_skeleton``/``quant_skeleton``), so save and load flatten to
    the same key paths for any registered kind.

    ``reducer`` is the Reduce stage's kind (None = no projection). The
    proj travels as the kind's RAW params pytree — unwrapped from the
    ``Reducer`` union at save time — so qpad snapshots keep the exact
    ``proj[0]``/``proj[1]`` key paths of pre-zoo checkpoints; load
    rewraps."""
    ops = get_ops(kind)
    proj = (get_reducer_ops(reducer).skeleton(_L)
            if reducer is not None else None)
    if not streaming:
        # the flat-alias case (no Reduce stage: payload IS the corpus
        # array) is not re-saved; restore re-points it at the corpus
        payload = None if flat_alias else ops.payload_skeleton(_L)
        return {"state": EngineState(
            corpus=_L, proj=proj, index=Index(kind, payload))}
    opt = {f: (_L if f in store_fields else None) for f in _OPT_STORE_FIELDS}
    store = StreamStore(
        corpus=_L, row_ids=_L, n_rows=_L, dead=_L,
        delta_vectors=_L, delta_ids=_L, delta_count=_L, **opt)
    frozen = FrozenParams(proj=proj,
                          quant=Index(kind, ops.quant_skeleton(_L)))
    return {"store": store, "frozen": frozen}


def _host_template(skeleton, path: str, overlay: Optional[str] = None):
    """Fill a skeleton's placeholder leaves with the checkpoint's (host)
    arrays by pytree key path — shapes and dtypes come from the file.
    ``overlay`` (an incremental checkpoint) wins for the keys it holds."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(skeleton)
    over = {}
    if overlay is not None:
        with np.load(overlay) as d:
            over = {k: d[k] for k in d.files}
    with np.load(path) as data:
        leaves = []
        for kpath, _ in flat:
            key = jax.tree_util.keystr(kpath)
            if key in over:
                leaves.append(over[key])
                continue
            if key not in data:
                raise ValueError(
                    f"snapshot {path} is missing array {key!r} — was it "
                    "written by an incompatible version?")
            leaves.append(data[key])
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _prior_chain(directory: str):
    """Checkpoint basenames the existing manifest (if any) still
    references — retention must not unlink them while the new snapshot
    is mid-commit (crash between array write and metadata replace must
    leave the old chain fully loadable)."""
    meta_path = os.path.join(directory, SNAPSHOT_META)
    if not os.path.isfile(meta_path):
        return set()
    try:
        with open(meta_path) as f:
            meta = json.load(f)
    except (OSError, ValueError):
        return set()
    keep = set(meta.get("chain") or ())
    if meta.get("ckpt"):
        keep.add(meta["ckpt"])
    if meta.get("base_ckpt"):
        keep.add(meta["base_ckpt"])
    return keep


def _commit_meta(directory: str, meta: dict):
    tmp = os.path.join(directory, SNAPSHOT_META + ".tmp")
    with open(tmp, "w") as f:
        json.dump(meta, f, indent=2)
        f.flush()
        os.fsync(f.fileno())         # the commit point of the snapshot
    os.replace(tmp, os.path.join(directory, SNAPSHOT_META))


def save_engine(engine: SearchEngine, directory: str,
                incremental: bool = False) -> str:
    """Snapshot ``engine`` (spec + config + arrays) into ``directory``.

    Returns the checkpoint path. Raises if the dense arrays are gone
    (``shard(donate=True)``) — snapshot before donating, or snapshot the
    streaming store, which always stays dense.

    The write is **crash-consistent across the directory**: arrays land
    under a fresh (incremented) checkpoint step, the metadata commits via
    fsync'd temp-file + ``os.replace``, and only *after* that commit is
    the engine's WAL (when this is its durable directory) marked with a
    SNAPSHOT record and truncated up to the saved sequence — a crash at
    any point leaves either the old snapshot + full log or the new
    snapshot + tail, never a mix.

    ``incremental=True`` (streaming, durable, same-directory saves only)
    writes a **delta-only** checkpoint — the ``_INC_STORE_FIELDS``
    arrays plus the WAL position — whose manifest chains back to the
    newest full snapshot; see ``SearchEngine.save``. Each incremental
    carries the *complete current* delta/tombstone/id-map state, so the
    newest link supersedes the older ones: resolution always reads
    exactly two files (base + newest incremental). The chained base pins
    the WAL truncation floor: a follower seeded from the base artifact
    still needs every record past the base's ``wal_seq``.
    """
    if incremental:
        return _save_incremental(engine, directory)
    streaming = engine.store is not None
    if not streaming and engine.state is None:
        raise RuntimeError(
            "nothing to save: the dense EngineState was released by "
            "shard(donate=True) — call save() before donating the dense "
            "buffers")
    if streaming and engine._compact_future is not None:
        engine.finish_compact()      # snapshot the post-swap store
    wal = None
    wal_seq = -1
    if (engine._wal is not None
            and os.path.abspath(directory) == engine._durable_dir):
        wal = engine._wal
        wal.sync()                   # everything the snapshot covers is on
        wal_seq = wal.last_seq       # disk before the snapshot claims it
    elif engine._wal is not None:
        # foreign-directory snapshot of a durable primary: record the WAL
        # position anyway — it is the seed point a follower built from
        # this artifact catches up from
        engine._wal.sync()
        wal_seq = engine._wal.last_seq
    elif engine._role == "follower":
        wal_seq = engine._applied_seq    # a follower's position is its
        #                                  applied seq, not a local log
    cfg = engine.config
    spec = engine.spec
    flat_alias = False
    store_fields = []
    if streaming:
        proj = engine.frozen.proj
        # persist the RAW reducer params (not the tagged union): qpad key
        # paths stay identical to pre-zoo snapshots; load rewraps
        frozen = engine.frozen._replace(
            proj=proj.params if proj is not None else None)
        tree = {"store": engine.store, "frozen": frozen}
        store_fields = [f for f in _OPT_STORE_FIELDS
                        if getattr(engine.store, f) is not None]
    else:
        state = engine.state
        proj = state.proj
        state = state._replace(
            proj=proj.params if proj is not None else None)
        if state.index.kind == "flat" and state.index.payload is state.corpus:
            # don't write the same rows twice; restore re-aliases
            flat_alias = True
            state = state._replace(index=Index("flat", None))
        tree = {"state": state}
    has_proj = proj is not None
    red_kind = proj.kind if proj is not None else None
    # fresh step per save: the metadata names its checkpoint, so a crash
    # between the array write and the metadata commit leaves the previous
    # (still-named, still-retained) snapshot fully intact
    prev = latest_checkpoint(directory)
    step = checkpoint_step(prev) + 1 if prev else 0
    path = save_checkpoint(directory, step, tree,
                           protect=sorted(_prior_chain(directory)))
    engine._crash("snapshot_arrays")
    if wal is not None:
        # the mark is itself covered by wal_seq: a no-op on replay, so
        # writing it before the metadata commit is safe either way the
        # commit goes — and afterwards replay starts strictly past it
        wal_seq = wal.append(RT_SNAPSHOT, str(wal_seq).encode())
        wal.sync()
    meta = {
        "schema": _SCHEMA,
        "spec": format_spec(spec),
        "kind": spec.kind,
        "streaming": streaming,
        "has_proj": has_proj,
        "reducer": red_kind,
        "flat_alias": flat_alias,
        "store_fields": store_fields,
        "ckpt": os.path.basename(path),
        "runtime": {f: getattr(cfg, f) for f in _RUNTIME_FIELDS},
        "stream": (dataclasses.asdict(cfg.stream)
                   if cfg.stream is not None else None),
        "wal_seq": wal_seq,
        "durability": (dataclasses.asdict(engine._durability)
                       if wal is not None else None),
        "incremental": False,
        "chain": [os.path.basename(path)],
    }
    _commit_meta(directory, meta)
    engine._crash("snapshot_commit")
    if wal is not None:
        # snapshot durable: records at or before wal_seq are dead weight,
        # and this full snapshot is the new chain base — the floor moves
        wal.pin_floor(wal_seq)
        wal.truncate(wal_seq)
    if streaming:
        engine._base_ref = {"dir": os.path.abspath(directory),
                            "ckpt": os.path.basename(path),
                            "wal_seq": wal_seq,
                            "chain": [os.path.basename(path)]}
        engine._base_dirty = False
    engine._snap_counters["full"] += 1
    engine._snap_counters["last_bytes"] = os.path.getsize(path)
    engine._snap_counters["chain_depth"] = 0
    return path


def _save_incremental(engine: SearchEngine, directory: str) -> str:
    """The delta-only save path (``save_engine(..., incremental=True)``):
    validates the chain invariants, writes only the ``_INC_STORE_FIELDS``
    arrays, and commits a manifest chained to the existing base."""
    directory_abs = os.path.abspath(directory)
    if engine.store is None:
        raise ValueError(
            "incremental snapshots cover the streaming delta state; this "
            "engine is read-only — its one full snapshot already is "
            "minimal. Use engine.save(dir).")
    if engine._compact_future is not None:
        engine.finish_compact()      # lands base changes -> dirties base
    if engine._wal is None or engine._durable_dir != directory_abs:
        raise ValueError(
            "incremental save needs a durable base: the chain's WAL "
            "position only means something against the log in the same "
            "directory. Call engine.durable(dir) (which takes the full "
            "base snapshot) and then save(dir, incremental=True).")
    base = engine._base_ref
    if base is None or base["dir"] != directory_abs:
        raise ValueError(
            "incremental save without a base snapshot in this directory: "
            "call engine.save(dir) once (full) before chaining "
            "incrementals onto it.")
    if engine._base_dirty:
        raise ValueError(
            "the base arrays changed since the base snapshot (a "
            "compaction, vacuum, rebuild or grow rewrote them), so a "
            "delta-only snapshot can no longer restore this engine — "
            "take a full snapshot (engine.save(dir)) to start a new "
            "chain.")
    base_path = os.path.join(directory, base["ckpt"])
    if not os.path.isfile(base_path):
        raise FileNotFoundError(
            f"the chain's base checkpoint {base['ckpt']!r} is gone from "
            f"{directory!r}; take a full snapshot to start a new chain")
    wal = engine._wal
    wal.sync()
    wal_seq = wal.last_seq
    flat, _ = jax.tree_util.tree_flatten_with_path({"store": engine.store})
    arrays = {jax.tree_util.keystr(kpath): np.asarray(leaf)
              for kpath, leaf in flat
              if kpath[-1].name in _INC_STORE_FIELDS}
    prev = latest_checkpoint(directory)
    step = checkpoint_step(prev) + 1 if prev else 0
    protect = set(base["chain"]) | {base["ckpt"]}
    path = save_arrays(directory, step, arrays, protect=sorted(protect))
    engine._crash("snapshot_arrays")
    wal_seq = wal.append(RT_SNAPSHOT, str(wal_seq).encode())
    wal.sync()
    cfg = engine.config
    spec = engine.spec
    chain = list(base["chain"]) + [os.path.basename(path)]
    meta = {
        "schema": _SCHEMA,
        "spec": format_spec(spec),
        "kind": spec.kind,
        "streaming": True,
        "has_proj": engine.frozen.proj is not None,
        "reducer": (engine.frozen.proj.kind
                    if engine.frozen.proj is not None else None),
        "flat_alias": False,
        "store_fields": [f for f in _OPT_STORE_FIELDS
                         if getattr(engine.store, f) is not None],
        "ckpt": os.path.basename(path),
        "runtime": {f: getattr(cfg, f) for f in _RUNTIME_FIELDS},
        "stream": (dataclasses.asdict(cfg.stream)
                   if cfg.stream is not None else None),
        "wal_seq": wal_seq,
        "durability": dataclasses.asdict(engine._durability),
        "incremental": True,
        "base_ckpt": base["ckpt"],
        "base_wal_seq": base["wal_seq"],
        "chain": chain,
    }
    _commit_meta(directory, meta)
    engine._crash("snapshot_commit")
    # records past the BASE's position must survive truncation: they are
    # what re-seeds a follower built from the base artifact (and what a
    # re-resolved chain replays past the newest incremental)
    wal.pin_floor(base["wal_seq"])
    wal.truncate(wal_seq)
    engine._base_ref = dict(base, chain=chain)
    engine._snap_counters["incremental"] += 1
    engine._snap_counters["last_bytes"] = os.path.getsize(path)
    engine._snap_counters["chain_depth"] = len(chain) - 1
    return path


def load_engine(directory: str, mesh: Optional[Mesh] = None,
                axis: str = "data", role: str = "primary",
                **runtime_overrides) -> SearchEngine:
    """Restore a ``save_engine`` snapshot into a serving ``SearchEngine``.

    The spec string in ``engine.json`` rebuilds the config; the arrays are
    restored through ``repro.runtime.checkpoint`` into a pytree whose
    structure is derived from the spec — so the engine comes back with
    identical shapes, dtypes, and treedefs, and therefore compiles no new
    program shapes vs the engine that was saved. An incremental manifest
    resolves its chain: base arrays from the referenced full checkpoint,
    delta/tombstone/id-map arrays from the newest incremental.

    ``mesh`` restores straight onto a device mesh: every leaf is placed
    by ``restore_resharded`` and the engine is then partitioned along
    ``axis`` (read-only engines donate the transient dense copy; a
    streaming engine shards its base and keeps the replicated write
    path). ``runtime_overrides`` replace persisted runtime knobs
    (``query_bucket=...``, etc.).

    ``role="follower"`` builds a read replica: the snapshot's arrays and
    WAL *position* are restored, but the local log is neither replayed
    nor resumed (the directory may be a shipped copy; a follower's
    history comes from its primary via
    ``durability.replication.catch_up``, which also tracks the position
    from the snapshot's ``wal_seq``). Follower engines reject local
    writes.
    """
    if role not in ("primary", "follower"):
        raise ValueError(
            f"unknown role {role!r}; expected 'primary' or 'follower'")
    meta_path = os.path.join(directory, SNAPSHOT_META)
    if not os.path.isfile(meta_path):
        raise FileNotFoundError(
            f"no engine snapshot at {directory!r} (missing {SNAPSHOT_META})")
    with open(meta_path) as f:
        meta = json.load(f)
    if meta.get("schema") != _SCHEMA:
        raise ValueError(
            f"unknown snapshot schema {meta.get('schema')!r} in {meta_path}")
    if meta.get("ckpt"):
        # the metadata names its checkpoint: immune to a stray newer
        # array file whose metadata commit never happened (crash mid-save)
        path = os.path.join(directory, meta["ckpt"])
        if not os.path.isfile(path):
            raise FileNotFoundError(
                f"snapshot metadata names missing checkpoint {path!r}")
    else:
        path = latest_checkpoint(directory)
        if path is None:
            raise FileNotFoundError(f"no checkpoint file in {directory!r}")
    overlay = None
    if meta.get("incremental"):
        # chain resolution: the named ckpt is delta-only; the base holds
        # everything else. The newest incremental supersedes older links.
        overlay = path
        path = os.path.join(directory, meta["base_ckpt"])
        if not os.path.isfile(path):
            raise FileNotFoundError(
                f"incremental snapshot chain is broken: base checkpoint "
                f"{meta['base_ckpt']!r} is missing from {directory!r} "
                f"(chain {meta.get('chain')}); re-seed from a full "
                "snapshot")
    spec = parse_spec(meta["spec"])
    if "stream" in runtime_overrides:
        raise ValueError(
            "stream= cannot be overridden at load: the StreamConfig's "
            "capacities are baked into the saved store's array shapes — "
            "restore, then compact/rebuild to re-provision")
    runtime = {f: v for f, v in meta["runtime"].items()
               if f not in _RETIRED_RUNTIME_FIELDS}
    if meta["stream"] is not None:
        skw = dict(meta["stream"])
        if skw.get("policy") is not None:
            skw["policy"] = PolicyConfig(**skw["policy"])
        runtime["stream"] = StreamConfig(**skw)
    runtime.update(runtime_overrides)
    config = config_from_spec(spec, **runtime)
    # pre-zoo snapshots carry has_proj only: their one reducer was qpad
    red_kind = meta.get(
        "reducer", "qpad" if meta.get("has_proj") else None)
    skeleton = _snapshot_skeleton(meta["kind"], red_kind,
                                  meta["streaming"], meta["flat_alias"],
                                  store_fields=meta.get("store_fields", ()))
    template = _host_template(skeleton, path, overlay)
    if mesh is None:
        tree = restore_checkpoint(path, template, overlay=overlay)
    else:
        # checkpoints are shard-agnostic: place every leaf directly onto
        # the target mesh (replicated; the layout pass below partitions)
        shardings = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), template)
        tree = restore_resharded(path, template, shardings, overlay=overlay)

    def _rewrap(raw):      # raw params from disk -> tagged Reducer union
        return Reducer(red_kind, raw) if red_kind is not None else None

    if meta["streaming"]:
        frozen = tree["frozen"]
        frozen = frozen._replace(proj=_rewrap(frozen.proj))
        engine = SearchEngine._restore(config, store=tree["store"],
                                       frozen=frozen)
    else:
        state = tree["state"]
        state = state._replace(proj=_rewrap(state.proj))
        if meta["flat_alias"]:
            state = state._replace(index=Index("flat", state.corpus))
        engine = SearchEngine._restore(config, state=state)
    wal_seq = meta.get("wal_seq", -1)
    engine._applied_seq = wal_seq
    if meta["streaming"]:
        # the loaded manifest's chain is the one this engine may extend
        # with save(dir, incremental=True)
        engine._base_ref = {
            "dir": os.path.abspath(directory),
            "ckpt": meta.get("base_ckpt") or meta["ckpt"],
            "wal_seq": (meta.get("base_wal_seq", wal_seq)
                        if meta.get("incremental") else wal_seq),
            "chain": list(meta.get("chain") or [meta["ckpt"]]),
        }
        engine._snap_counters["chain_depth"] = (
            len(engine._base_ref["chain"]) - 1)
    if role == "follower":
        # a replica: restore position only — no local replay (the
        # shipped history comes from the primary via catch_up), no
        # local WAL writer (followers never append)
        engine._role = "follower"
    elif meta.get("durability") is not None:
        # crash recovery: replay the WAL tail (records after the saved
        # sequence) through the engine's own write programs, then resume
        # appending to the same log — recovered == never-crashed
        from .durability.recovery import replay
        dcfg = DurabilityConfig(**meta["durability"])
        wal_dir = os.path.join(directory, "wal")
        stats = replay(engine, wal_dir, after_seq=wal_seq)
        engine._replayed = stats.records
        if stats.records:
            engine._applied_seq = stats.last_seq
        engine._wal = Wal(wal_dir, dcfg, resume=True)
        engine._durability = dcfg
        engine._durable_dir = os.path.abspath(directory)
        if meta["streaming"]:
            # the floor pin is engine state, not log state: re-pin from
            # the manifest so chained truncation keeps holding past a
            # process restart
            engine._wal.pin_floor(engine._base_ref["wal_seq"])
    if mesh is not None:
        engine.shard(mesh, axis=axis,
                     donate=not meta["streaming"])
    return engine
