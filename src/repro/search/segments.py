"""Mutable serving state: delta segments, tombstones, and jit-compiled
compaction over a frozen base index.

The read-only engine (``repro.search.serve``) freezes the corpus at build
time; real deployments continuously upsert and delete vectors. This module
is the **write path**: an LSM-flavored two-layer layout whose every
operation is a pure jit-stable function over fixed-shape arrays, so a
serving process never recompiles per write.

Layers
------

* **base** — the built index arrays, re-padded to a fixed *row capacity*
  ``n_cap >= N`` (and, for IVF layouts, per-cell *pad slack* on the posting
  lists) so compaction can append without changing any array shape.
  ``row_ids (n_cap,)`` maps base row -> external id (-1 = unallocated
  slot); ``dead (n_cap,) bool`` is the **tombstone bitmap** masking
  deleted/overwritten rows out of every scan.
* **delta** — a fixed-capacity segment of recently upserted vectors,
  scanned *exactly* in the reduced space (no quantization staleness for
  fresh rows). ``delta_ids (cap,)`` holds external ids, -1 = empty slot or
  deletion hole; ``delta_count`` is the append pointer.

Quantizers (MPAD projection + the index kind's frozen payload — coarse
centroids, PQ codebooks and their LUT factorization — carried as the
tagged ``Index`` union in ``FrozenParams.quant``) are **frozen** at build
time; compaction re-codes delta rows against them, never retrains — which
is exactly what keeps the compiled serve programs cache-valid across the
whole write lifecycle.

Operations (all pure; the engine jits them with the store donated, so XLA
aliases the buffers and the ``.at[]`` writes happen in place):

* ``upsert_fn(store, frozen, ids, vectors)`` — tombstone any base copy of
  each id, overwrite an existing delta slot for the id or append a new
  one. Later rows of a batch win over earlier ones (sequential
  semantics); ``id == -1`` rows are no-ops, so batches can be padded to
  fixed bucket shapes.
* ``delete_fn(store, ids)`` — tombstone base copies, punch holes in the
  delta. Deleting an absent id is a no-op.
* ``compact_fn(store, frozen)`` — fold the delta into the base:
  re-encode against the frozen quantizers (``IndexOps.encode_delta``),
  append rows into the row store and the cell-major
  ``codes_cell``/``bias_cell`` mirrors, extend posting lists into their
  pad slack, clear the delta. All-or-nothing: if the append would
  overflow the row capacity or any cell's slack, the state is returned
  unchanged with a nonzero dropped-count and the caller grows the store
  host-side (``grow_store`` — a rare, amortized reshape that is the only
  recompile point in the subsystem).

``rebuild_state`` builds a fresh read-only ``EngineState`` over any row
set with the same frozen quantizers — the from-scratch oracle the
streaming equivalence tests (and offline full rebuilds) compare against.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .durability.policy import PolicyConfig
from .ivf import sq_dists
from .ivfpq import encode_residuals
from .pq import _nearest_codes
from .reducers import Reducer, reduce_vectors, reducer_dim
from .registry import Index, _pad_cells, _pad_rows, get_ops

__all__ = ["StreamConfig", "StreamStore", "MutableEngineState",
           "FrozenParams", "make_mutable", "upsert_fn", "delete_fn",
           "compact_fn", "grow_store", "live_mask", "rebuild_state",
           "encode_pq", "ivfpq_encode"]


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Write-path knobs (``SearchEngine.streaming`` / ``ServeConfig.stream``
    enable streaming)."""
    delta_capacity: int = 256        # fixed delta segment size (rows)
    compact_threshold: float = 0.75  # auto-compact when the delta holds
    #                                  this fraction of its capacity
    row_capacity: Optional[int] = None   # total base row slots; None =
    #                                      N + 4 * delta_capacity
    cell_slack: Optional[int] = None     # extra posting slots per cell for
    #                                      compaction appends; None =
    #                                      delta_capacity
    write_bucket: int = 64           # min padded write-batch size; ragged
    #                                  batches round up to powers of two
    background_compact: bool = False     # double-buffered compaction: fold
    #                                      a copy off-thread while searches
    #                                      keep serving the old store, then
    #                                      swap atomically
    policy: Optional[PolicyConfig] = None    # maintenance thresholds
    #                                          (tombstone density, drift,
    #                                          headroom); None = defaults

    def __post_init__(self):
        if self.delta_capacity < 1:
            raise ValueError("delta_capacity must be >= 1")
        if not (0.0 < self.compact_threshold <= 1.0):
            raise ValueError("compact_threshold must be in (0, 1]")
        if self.cell_slack is not None and self.cell_slack < 1:
            raise ValueError("cell_slack must be >= 1")
        if self.write_bucket < 1:
            raise ValueError("write_bucket must be >= 1")
        if self.policy is not None and not isinstance(self.policy,
                                                      PolicyConfig):
            raise TypeError(
                "StreamConfig.policy must be a "
                "repro.search.durability.PolicyConfig (or None)")


class FrozenParams(NamedTuple):
    """Build-time quantizers shared by base and delta; never mutated (and
    never donated), so they can alias the original ``EngineState``.

    ``quant`` is the tagged union: the index kind plus its frozen payload
    (None for flat, coarse centroids for ivf, ``PQQuant`` /
    ``IVFPQQuant`` for the coded kinds). The accessor properties give the
    per-array views the scan/encode code reads.
    """
    proj: Optional[Reducer]                       # fitted Reduce stage
    quant: Index                                  # kind + frozen quantizers

    @property
    def kind(self) -> str:
        return self.quant.kind

    @property
    def centroids(self) -> Optional[jax.Array]:
        q = self.quant.payload
        if self.quant.kind == "ivf":
            return q
        return getattr(q, "centroids", None)

    @property
    def codebooks(self) -> Optional[jax.Array]:
        return getattr(self.quant.payload, "codebooks", None)

    @property
    def lut_w(self) -> Optional[jax.Array]:
        return getattr(self.quant.payload, "lut_w", None)

    @property
    def cbnorm(self) -> Optional[jax.Array]:
        return getattr(self.quant.payload, "cbnorm", None)


class StreamStore(NamedTuple):
    """Every mutable leaf of the streaming engine, one fixed-shape pytree.

    Internal id space: base row r in [0, n_cap) | delta slot s as
    ``n_cap + s``. External ids live in ``row_ids``/``delta_ids``.
    """
    corpus: jax.Array               # (n_cap, D) original-space row store
    row_ids: jax.Array              # (n_cap,) int32 row -> external id, -1
    n_rows: jax.Array               # () int32 allocated base rows
    dead: jax.Array                 # (n_cap,) bool tombstone bitmap
    reduced: Optional[jax.Array]    # (n_cap, m) scan-space rows (None = no
    #                                 projection; scan from ``corpus``)
    codes: Optional[jax.Array]      # (n_cap, M) uint8/int32 pq/ivfpq codes
    bias: Optional[jax.Array]       # (n_cap,) f32 ivfpq cross term
    lists: Optional[jax.Array]      # (nlist, mc_cap) posting lists, -1 pad
    codes_cell: Optional[jax.Array]  # (nlist, mc_cap, M) cell-major codes
    bias_cell: Optional[jax.Array]   # (nlist, mc_cap) cell-major bias
    delta_vectors: jax.Array        # (cap, D) original-space delta rows
    delta_reduced: Optional[jax.Array]  # (cap, m) scan-space (None = no proj)
    delta_ids: jax.Array            # (cap,) int32 external ids, -1 = empty
    delta_count: jax.Array          # () int32 append pointer


# the store IS the mutable engine state (base + delta + tombstones); the
# serving-layer name for the same pytree
MutableEngineState = StreamStore


def live_mask(store: StreamStore) -> jax.Array:
    """(n_cap,) bool: base rows that are allocated and not tombstoned."""
    return (store.row_ids >= 0) & ~store.dead


def _project(proj, vectors: jax.Array) -> jax.Array:
    return reduce_vectors(proj, vectors)


def encode_pq(codebooks: jax.Array, x: jax.Array) -> jax.Array:
    """Nearest-codeword PQ codes for rows ``x``: (B, M) int32.

    The same argmin as ``build_pq``'s final assignment, so a vector encodes
    to identical codes whether it arrived at build time or at compaction.
    """
    return _nearest_codes(codebooks, x).astype(jnp.int32)


def ivfpq_encode(centroids: jax.Array, codebooks: jax.Array, x: jax.Array):
    """Coarse-assign + residual-PQ-encode rows ``x`` against frozen
    quantizers. Returns (assign (B,), codes (B, M) int32, bias (B,) f32) —
    the exact per-row payload ``build_ivfpq`` computes at build time.
    """
    assign = jnp.argmin(sq_dists(x, centroids), axis=1)
    codes, bias, _ = encode_residuals(codebooks, centroids,
                                      x - centroids[assign], assign)
    return assign, codes.astype(jnp.int32), bias.astype(jnp.float32)


def make_mutable(state, config: StreamConfig
                 ) -> Tuple[StreamStore, FrozenParams]:
    """Re-lay an immutable ``EngineState`` into (StreamStore, FrozenParams).

    Every store leaf is a fresh buffer (padded or copied —
    ``IndexOps.store_parts`` lays out the kind-specific base arrays), so
    the engine can donate the store to the write programs without
    invalidating the original state or the frozen quantizers.
    """
    kind = state.index.kind
    ops = get_ops(kind)
    n, d = state.corpus.shape
    cap = config.delta_capacity
    n_cap = config.row_capacity or n + 4 * cap
    if n_cap <= n:
        raise ValueError(
            f"row_capacity {n_cap} must exceed the corpus size {n} "
            "(compaction needs append slack)")
    proj = state.proj
    cell_slack = config.cell_slack if config.cell_slack is not None else cap
    parts, quant = ops.store_parts(state, n_cap, cell_slack)
    m_dim = reducer_dim(proj) if proj is not None else d
    store = StreamStore(
        corpus=_pad_rows(state.corpus, n_cap),
        row_ids=_pad_rows(jnp.arange(n, dtype=jnp.int32), n_cap, fill=-1),
        n_rows=jnp.asarray(n, jnp.int32),
        dead=jnp.zeros((n_cap,), bool),
        reduced=parts.get("reduced"), codes=parts.get("codes"),
        bias=parts.get("bias"), lists=parts.get("lists"),
        codes_cell=parts.get("codes_cell"),
        bias_cell=parts.get("bias_cell"),
        delta_vectors=jnp.zeros((cap, d), jnp.float32),
        delta_reduced=(jnp.zeros((cap, m_dim), jnp.float32)
                       if proj is not None else None),
        delta_ids=jnp.full((cap,), -1, jnp.int32),
        delta_count=jnp.zeros((), jnp.int32))
    return store, FrozenParams(proj=proj, quant=Index(kind, quant))


# --- the write path (pure; engine jits with the store donated) ---------------

def upsert_fn(store: StreamStore, frozen: FrozenParams, ids: jax.Array,
              vectors: jax.Array) -> Tuple[StreamStore, jax.Array]:
    """Apply a padded upsert batch: (ids (B,) int32 with -1 = no-op pad,
    vectors (B, D) f32). Sequential batch semantics (later rows win).

    Returns (store, dropped): ``dropped`` counts valid rows that found the
    delta segment full (the engine pre-compacts so this stays 0; direct
    callers must check it and compact + retry the remainder).
    """
    ids = jnp.asarray(ids, jnp.int32)
    vectors = jnp.asarray(vectors, jnp.float32)
    valid = ids >= 0
    # tombstone any base copy of each upserted id (vectorized over batch)
    hit = (store.row_ids[:, None] == ids[None, :]) & valid[None, :]
    dead = store.dead | hit.any(axis=1)
    cap = store.delta_ids.shape[0]
    slots = jnp.arange(cap)
    has_red = store.delta_reduced is not None
    red = _project(frozen.proj, vectors) if has_red else vectors

    def body(carry, x):
        d_ids, d_vec, d_red, count, dropped = carry
        i, v, vr, val = x
        match = (d_ids == i) & (slots < count) & val
        exists = match.any()
        slot = jnp.where(exists, jnp.argmax(match), count)
        slot = jnp.where(val, slot, cap)          # pads scatter out of range
        d_ids = d_ids.at[slot].set(i, mode="drop")
        d_vec = d_vec.at[slot].set(v, mode="drop")
        if d_red is not None:
            d_red = d_red.at[slot].set(vr, mode="drop")
        appended = val & ~exists & (slot < cap)
        lost = val & ~exists & (slot >= cap)      # delta full
        return (d_ids, d_vec, d_red, count + appended.astype(count.dtype),
                dropped + lost.astype(dropped.dtype)), None

    init = (store.delta_ids, store.delta_vectors,
            store.delta_reduced if has_red else None, store.delta_count,
            jnp.zeros((), jnp.int32))
    (d_ids, d_vec, d_red, count, dropped), _ = jax.lax.scan(
        body, init, (ids, vectors, red, valid))
    out = store._replace(dead=dead, delta_ids=d_ids, delta_vectors=d_vec,
                         delta_reduced=d_red, delta_count=count)
    return out, dropped


def delete_fn(store: StreamStore, ids: jax.Array) -> StreamStore:
    """Apply a padded delete batch (ids (B,) int32, -1 = no-op pad):
    tombstone base rows, punch delta holes. Absent ids are no-ops."""
    ids = jnp.asarray(ids, jnp.int32)
    valid = ids >= 0
    hit = (store.row_ids[:, None] == ids[None, :]) & valid[None, :]
    dead = store.dead | hit.any(axis=1)
    kill = ((store.delta_ids[:, None] == ids[None, :])
            & valid[None, :]).any(axis=1)
    return store._replace(
        dead=dead, delta_ids=jnp.where(kill, -1, store.delta_ids))


def compact_fn(store: StreamStore, frozen: FrozenParams
               ) -> Tuple[StreamStore, jax.Array]:
    """Fold the delta segment into the base; returns (store, dropped).

    All-or-nothing: when the append would overflow the row capacity or any
    posting cell's pad slack, the state comes back unchanged and
    ``dropped`` (the number of rows that could not be folded) is nonzero —
    the caller grows the store host-side and retries. Quantizers are
    frozen: delta rows are re-coded against them
    (``IndexOps.encode_delta`` on ``frozen.quant.kind``), so no
    serve-program shape or constant changes.
    """
    ops = get_ops(frozen.quant.kind)
    cap = store.delta_ids.shape[0]
    n_cap = store.corpus.shape[0]
    slots = jnp.arange(cap)
    alive = (slots < store.delta_count) & (store.delta_ids >= 0)
    n_alive = jnp.sum(alive.astype(jnp.int32))
    pos = jnp.cumsum(alive.astype(jnp.int32)) - 1       # packed ordinal
    dest = store.n_rows + pos                           # target base row
    ok = store.n_rows + n_alive <= n_cap                # row-capacity check

    scan_rows = (store.delta_reduced if store.delta_reduced is not None
                 else store.delta_vectors)
    assign, codes, bias = ops.encode_delta(frozen, scan_rows)
    slot_pos = None
    if store.lists is not None:
        nlist, mc_cap = store.lists.shape
        counts = jnp.sum((store.lists >= 0).astype(jnp.int32), axis=1)
        onehot = (jax.nn.one_hot(assign, nlist, dtype=jnp.int32)
                  * alive[:, None].astype(jnp.int32))
        rank = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - onehot, assign[:, None], axis=1)[:, 0]
        slot_pos = counts[assign] + rank
        ok = ok & ~jnp.any(alive & (slot_pos >= mc_cap))  # cell-slack check

    write = ok & alive
    dest = jnp.where(write, dest, n_cap)                # OOB => dropped
    corpus = store.corpus.at[dest].set(store.delta_vectors, mode="drop")
    row_ids = store.row_ids.at[dest].set(store.delta_ids, mode="drop")
    reduced = (store.reduced.at[dest].set(store.delta_reduced, mode="drop")
               if store.reduced is not None else None)
    new_codes = (store.codes.at[dest].set(
        codes.astype(store.codes.dtype), mode="drop")
                 if store.codes is not None else None)
    new_bias = (store.bias.at[dest].set(bias, mode="drop")
                if store.bias is not None else None)
    lists = codes_cell = bias_cell = None
    if store.lists is not None:
        nlist = store.lists.shape[0]
        cell = jnp.where(write, assign, nlist)          # OOB => dropped
        lists = store.lists.at[cell, slot_pos].set(
            dest.astype(jnp.int32), mode="drop")
        if store.codes_cell is not None:
            codes_cell = store.codes_cell.at[cell, slot_pos].set(
                codes.astype(store.codes_cell.dtype), mode="drop")
            bias_cell = store.bias_cell.at[cell, slot_pos].set(
                bias, mode="drop")
    okw = ok.astype(jnp.int32)
    out = store._replace(
        corpus=corpus, row_ids=row_ids,
        n_rows=store.n_rows + okw * n_alive,
        reduced=reduced, codes=new_codes, bias=new_bias, lists=lists,
        codes_cell=codes_cell, bias_cell=bias_cell,
        delta_ids=jnp.where(ok, -1, store.delta_ids),
        delta_count=store.delta_count * (1 - okw))
    return out, (1 - okw) * n_alive


def grow_store(store: StreamStore, *, row_extra: int = 0,
               cell_extra: int = 0) -> StreamStore:
    """Host-side capacity growth (the compaction-overflow escape hatch).

    Pads the row store by ``row_extra`` rows and every posting cell by
    ``cell_extra`` slots. Shapes change, so downstream programs recompile
    once — size ``StreamConfig.row_capacity``/``cell_slack`` to make this
    rare.
    """
    n_cap = store.corpus.shape[0] + row_extra
    return store._replace(
        corpus=_pad_rows(store.corpus, n_cap),
        row_ids=_pad_rows(store.row_ids, n_cap, fill=-1),
        dead=_pad_rows(store.dead, n_cap, fill=False),
        reduced=(_pad_rows(store.reduced, n_cap)
                 if store.reduced is not None else None),
        codes=(_pad_rows(store.codes, n_cap)
               if store.codes is not None else None),
        bias=(_pad_rows(store.bias, n_cap)
              if store.bias is not None else None),
        lists=(_pad_cells(store.lists, cell_extra, fill=-1)
               if store.lists is not None else None),
        codes_cell=(_pad_cells(store.codes_cell, cell_extra)
                    if store.codes_cell is not None else None),
        bias_cell=(_pad_cells(store.bias_cell, cell_extra)
                   if store.bias_cell is not None else None))


def rebuild_state(frozen: FrozenParams, vectors: jax.Array, *,
                  index: Optional[str] = None, shards: int = 1):
    """Build a read-only ``EngineState`` over ``vectors`` with the FROZEN
    quantizers (no retraining) — the offline full-rebuild path and the
    from-scratch oracle of the streaming equivalence tests: after
    ``compact()``, streaming search over the survivors must return exactly
    what this state returns. ``index`` defaults to the frozen kind.
    """
    from .serve import EngineState

    kind = index if index is not None else frozen.quant.kind
    if kind != frozen.quant.kind:
        raise ValueError(
            f"index={kind!r} does not match the frozen quantizers "
            f"({frozen.quant.kind!r})")
    vectors = jnp.asarray(vectors, jnp.float32)
    reduced = _project(frozen.proj, vectors)
    payload = get_ops(kind).rebuild(frozen, reduced, shards)
    return EngineState(corpus=vectors, proj=frozen.proj,
                       index=Index(kind, payload))
