"""Streaming search: one XLA program over base index + delta segment +
tombstones.

``stream_search_fn`` is the mutable-engine counterpart of
``repro.search.serve.search_fn``: the same project -> probe/scan ->
re-rank pipeline, extended with

* a **tombstone mask** (``live = row_ids >= 0 & ~dead``) applied *before*
  every base top-k, so dead rows can never crowd live candidates out of
  the budget (for the coded indexes the mask rides the additive ``base``
  term, which is what lets the fused Pallas ADC-gather kernel serve the
  masked scan unchanged);
* an **exact delta scan** — recently upserted rows are scored with true
  squared distances in the scan space, so fresh writes are served at full
  fidelity before they are ever quantized;
* a **tombstone-masked merge** of the two layers in one internal id space
  (base row r | delta slot ``n_cap + s``), followed by the shared
  dedup'd exact re-rank (two-source gather) and a final map from internal
  ids to **external** ids.

The base scan dispatches on the frozen quantizers' kind
(``frozen.quant.kind``) through the ops registry
(``IndexOps.stream_scan``), so the streaming read path needs no per-kind
code here. Everything is shape-static in (n_cap, delta capacity, query
bucket), so a serving process upserting/deleting/compacting at full tilt
reuses one compiled program per (index kind, knobs, k, bucket) — pinned
by ``tests/test_stream.py``.

``sharded_stream_search_fn`` runs the same pipeline under ``shard_map``:
the base is partitioned exactly like read-only sharded serving
(``repro.parallel.engine.shard_stream``), while the delta segment,
tombstone bitmap, and id maps **replicate** — writes touch only
replicated leaves, so the sharded base stays valid between compactions
and every shard scans the delta identically (per-shard scan =
``IndexOps.local_scan`` with the replicated ``live`` mask).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .knn import _sq_dists, masked_topk
from .pq import _check_adc_args
from .reducers import reduce_vectors
from .registry import ScanParams, get_ops
from .segments import FrozenParams, StreamStore, live_mask
from .serve import (ShardedEngineState, _check_rerank_budget,
                    _dedupe_candidates)

__all__ = ["stream_search_fn", "sharded_stream_search_fn", "StreamReplica",
           "replica_from_store"]


class StreamReplica(NamedTuple):
    """The replicated (small, write-hot) leaves a sharded streaming search
    needs next to the sharded base: id maps, tombstones, and the delta
    segment. Rebuilt from the ``StreamStore`` per call — upserts and
    deletes never touch the sharded base."""
    row_ids: jax.Array                   # (n_cap,)
    dead: jax.Array                      # (n_cap,) bool
    delta_vectors: jax.Array             # (cap, D)
    delta_reduced: Optional[jax.Array]   # (cap, m)
    delta_ids: jax.Array                 # (cap,)
    delta_count: jax.Array               # ()


def replica_from_store(store: StreamStore) -> StreamReplica:
    """Project the write-hot replicated leaves out of a ``StreamStore``
    (free: a view of the same buffers, fresh every call so the sharded
    read path always serves the latest writes)."""
    return StreamReplica(
        row_ids=store.row_ids, dead=store.dead,
        delta_vectors=store.delta_vectors,
        delta_reduced=store.delta_reduced,
        delta_ids=store.delta_ids, delta_count=store.delta_count)


def _check_stream_backend(kind: str, backend: str):
    if kind in ("pq", "opq") and backend == "kernel":
        raise ValueError(
            f"streaming index={kind!r} needs backend='jnp': the "
            "shared-codes Pallas kernel has no masked entry point for an "
            "arbitrary tombstone bitmap (ivfpq folds the mask into the "
            "base term)")


def _delta_scan(qr, delta_scan_rows, delta_ids, delta_count, n_cap, n_cand):
    """Exact scan of the delta segment in the scan space; internal ids are
    offset by ``n_cap``. Empty/hole slots mask to (+inf, -1)."""
    cap = delta_ids.shape[0]
    alive = (jnp.arange(cap) < delta_count) & (delta_ids >= 0)
    d2 = _sq_dists(qr, delta_scan_rows)
    d2 = jnp.where(alive[None, :], d2, jnp.inf)
    ids = jnp.broadcast_to((n_cap + jnp.arange(cap))[None, :], d2.shape)
    return masked_topk(d2, ids, min(n_cand, cap))


def _stream_rerank(queries, corpus, delta_vectors, cand, k):
    """``exact_rerank`` with the two-source gather: internal ids below
    ``n_cap`` pull base corpus rows, the rest pull delta rows. Returns
    (dists (Q, k), INTERNAL ids (Q, k)); pads are (+inf, -1)."""
    cand, valid = _dedupe_candidates(cand)
    n_cap = corpus.shape[0]
    cap = delta_vectors.shape[0]
    isd = cand >= n_cap
    bv = jnp.take(corpus, jnp.clip(cand, 0, n_cap - 1), axis=0)
    dv = jnp.take(delta_vectors, jnp.clip(cand - n_cap, 0, cap - 1), axis=0)
    cv = jnp.where(isd[..., None], dv, bv)
    d2 = jnp.sum((cv - queries[:, None, :]) ** 2, axis=-1)
    d2 = jnp.where(valid, d2, jnp.inf)
    neg, sel = jax.lax.top_k(-d2, k)
    ids = jnp.take_along_axis(cand, sel, axis=1)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), ids


def _to_external(ids, row_ids, delta_ids):
    """Internal (base row | n_cap + slot) -> external ids; -1 pads kept."""
    n_cap = row_ids.shape[0]
    cap = delta_ids.shape[0]
    ext_b = jnp.take(row_ids, jnp.clip(ids, 0, n_cap - 1))
    ext_d = jnp.take(delta_ids, jnp.clip(ids - n_cap, 0, cap - 1))
    ext = jnp.where(ids >= n_cap, ext_d, ext_b)
    return jnp.where(ids >= 0, ext, -1)


def stream_search_fn(store: StreamStore, frozen: FrozenParams,
                     queries: jax.Array, k: int, *,
                     nprobe: int = 8, rerank: int = 64, backend: str = "jnp",
                     lut_dtype: str = "f32",
                     scan_cap: int = 0, prefilter: int = 0):
    """The mutable-engine query pipeline as one pure traceable function.

    project -> tombstone-masked base probe/scan (``IndexOps.stream_scan``
    on the frozen kind) -> exact delta scan -> merged top-C -> two-source
    exact re-rank -> external-id top-k.
    Returns (dists (Q, k), external ids (Q, k)); -1 ids pad short rows.
    """
    if scan_cap or prefilter:
        raise ValueError(
            "scan_cap/prefilter are read-only fast paths: the compact "
            "scan's posting-mass cap goes stale under writes and the "
            "pre-filter bounds ignore tombstones — leave both 0 on the "
            "streaming path")
    kind = frozen.quant.kind
    ops = get_ops(kind)
    _check_adc_args(backend, lut_dtype)
    _check_stream_backend(kind, backend)
    queries = jnp.asarray(queries, jnp.float32)
    with jax.named_scope("qpad.project"):
        qr = reduce_vectors(frozen.proj, queries)
    approximate = frozen.proj is not None or ops.lossy
    _check_rerank_budget(approximate, rerank, k)
    n_cand = rerank if approximate else k
    live = live_mask(store)
    n_cap = store.corpus.shape[0]
    p = ScanParams(nprobe=nprobe, backend=backend, lut_dtype=lut_dtype)
    with jax.named_scope("qpad.base_scan"):
        bd2, bids = ops.stream_scan(store, frozen, qr, n_cand, live, p)
    delta_scan_rows = (store.delta_reduced
                       if store.delta_reduced is not None
                       else store.delta_vectors)
    with jax.named_scope("qpad.delta_scan"):
        dd2, dids = _delta_scan(qr, delta_scan_rows, store.delta_ids,
                                store.delta_count, n_cap, n_cand)
    with jax.named_scope("qpad.merge"):
        md2, mids = masked_topk(jnp.concatenate([bd2, dd2], axis=1),
                                jnp.concatenate([bids, dids], axis=1),
                                n_cand)
    with jax.named_scope("qpad.rerank"):
        dists, internal = _stream_rerank(queries, store.corpus,
                                         store.delta_vectors, mids, k)
    return dists, _to_external(internal, store.row_ids, store.delta_ids)


# --- sharded streaming (base sharded, delta + tombstones replicated) ---------

def _stream_sharded_core(sbase: ShardedEngineState, repl: StreamReplica,
                         queries: jax.Array, *, k: int,
                         nprobe: int, rerank: int, backend: str,
                         lut_dtype: str, axis: str):
    """The shard_map body: masked per-shard base scan + replicated delta
    scan + distributed merge + two-source re-rank."""
    ops = get_ops(sbase.index.kind)
    queries = jnp.asarray(queries, jnp.float32)
    with jax.named_scope("qpad.project"):
        qr = reduce_vectors(sbase.proj, queries)
    approximate = sbase.proj is not None or ops.lossy
    _check_rerank_budget(approximate, rerank, k)
    n_cand = rerank if approximate else k
    live = (repl.row_ids >= 0) & ~repl.dead
    n_cap = repl.row_ids.shape[0]
    p = ScanParams(nprobe=nprobe, backend=backend, lut_dtype=lut_dtype)
    with jax.named_scope("qpad.base_scan"):
        d2, cand = ops.local_scan(sbase, qr, n_cand, p, axis, 0, live=live)
        d2g = jax.lax.all_gather(d2, axis, axis=1, tiled=True)
        idg = jax.lax.all_gather(cand, axis, axis=1, tiled=True)
        bd2, bids = masked_topk(d2g, idg, n_cand)
    delta_scan_rows = (repl.delta_reduced if repl.delta_reduced is not None
                       else repl.delta_vectors)
    with jax.named_scope("qpad.delta_scan"):
        dd2, dids = _delta_scan(qr, delta_scan_rows, repl.delta_ids,
                                repl.delta_count, n_cap, n_cand)
    md2, mids = masked_topk(jnp.concatenate([bd2, dd2], axis=1),
                            jnp.concatenate([bids, dids], axis=1), n_cand)
    # two-source re-rank: base rows scored by their owner shard, delta rows
    # scored identically on every shard; pmin assembles the full row
    cand2, valid = _dedupe_candidates(mids)
    n_loc = sbase.corpus.shape[0]
    cap = repl.delta_vectors.shape[0]
    off = jax.lax.axis_index(axis) * n_loc
    isd = cand2 >= n_cap
    local = cand2 - off
    own_base = valid & ~isd & (local >= 0) & (local < n_loc)
    bv = jnp.take(sbase.corpus, jnp.clip(local, 0, n_loc - 1), axis=0)
    dv = jnp.take(repl.delta_vectors,
                  jnp.clip(cand2 - n_cap, 0, cap - 1), axis=0)
    cv = jnp.where(isd[..., None], dv, bv)
    d2 = jnp.sum((cv - queries[:, None, :]) ** 2, axis=-1)
    d2 = jnp.where(own_base | (valid & isd), d2, jnp.inf)
    d2 = jax.lax.pmin(d2, axis)
    neg, sel = jax.lax.top_k(-d2, k)
    internal = jnp.take_along_axis(cand2, sel, axis=1)
    internal = jnp.where(jnp.isneginf(neg), -1, internal)
    dists = jnp.sqrt(jnp.maximum(-neg, 0.0))
    return dists, _to_external(internal, repl.row_ids, repl.delta_ids)


def sharded_stream_search_fn(sbase: ShardedEngineState, repl: StreamReplica,
                             queries: jax.Array, k: int, *, mesh: Mesh,
                             axis: str = "data",
                             nprobe: int = 8, rerank: int = 64,
                             backend: str = "jnp", lut_dtype: str = "f32",
                             scan_cap: int = 0, prefilter: int = 0):
    """``stream_search_fn`` with the base partitioned over ``mesh``.

    Same results as the single-device streaming search on the unsharded
    store: the per-shard masked scans keep a full local top-C (so the
    merged base candidate set is exact), and the delta scan is replicated
    math. Jit with ``mesh``/``axis`` static.
    """
    from repro.parallel.sharding import engine_state_specs
    if scan_cap or prefilter:
        raise ValueError(
            "scan_cap/prefilter are single-device read-only fast paths — "
            "leave both 0 on the sharded streaming path")
    _check_stream_backend(sbase.index.kind, backend)
    base_specs = engine_state_specs(sbase, axis)
    repl_specs = StreamReplica(*[None if getattr(repl, f) is None else P()
                                 for f in StreamReplica._fields])
    core = functools.partial(
        _stream_sharded_core, k=k, nprobe=nprobe, rerank=rerank,
        backend=backend, lut_dtype=lut_dtype, axis=axis)
    f = jax.shard_map(core, mesh=mesh,
                      in_specs=(base_specs, repl_specs, P()),
                      out_specs=(P(), P()), check_vma=False)
    return f(sbase, repl, queries)
