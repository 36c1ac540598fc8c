"""The tagged index union and the per-kind operation registry.

One ``Index`` pytree — a static ``kind`` tag plus the kind's stage
payload — replaces the old four-way ``Optional[IVFIndex] /
Optional[PQIndex] / Optional[IVFPQIndex] / Optional[reduced]`` fields
that ``EngineState``, ``ShardedEngineState``, and ``FrozenParams`` each
carried (and that every scan site re-dispatched on with if/elif chains).
The tag lives in the pytree's **aux data**, so it is static under
``jax.jit`` and keys compile caches through the treedef; the payload is
ordinary array state that shards, donates, and serialises.

Each kind registers one ``IndexOps`` entry holding every operation the
serving stack dispatches on:

    build                train the payload over the (reduced) corpus
    scan                 single-device probe/scan       (search_fn)
    local_scan           shard-local scan, global ids   (sharded serving,
                         also the streaming sharded base scan via live=)
    stream_scan          tombstone-masked base scan     (stream_search_fn)
    shard_payload        host-side sharded re-layout    (shard_engine)
    payload_specs        PartitionSpec tree for the sharded payload
    store_parts          StreamStore layout + frozen quantizer payload
    encode_delta         re-code delta rows on frozen quantizers (compact)
    rebuild              payload from frozen quantizers (rebuild_state)
    stream_base_payload  dense payload over a StreamStore (shard_stream)

Adding a future index kind (HNSW, additive quantizers, ...) is one
``register_index(IndexOps(...))`` call — no engine, stream, or sharding
edits. The ``opq`` kind below is the existence proof: a learned
orthogonal rotation (alternating Procrustes / assignment, OPQ-style)
fitted before PQ coding, registered as one entry that delegates every
scan to the plain-PQ ADC/LUT/kernel paths on the rotated query.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .ivf import (IVFIndex, build_ivf, cell_vectors, ivf_local_scan,
                  ivf_scan, probe_cells, sq_dists)
from .ivfpq import (IVFPQIndex, build_ivfpq, ivfpq_adc_scan,
                    ivfpq_compact_scan, ivfpq_local_scan, ivfpq_scan)
from .knn import _sq_dists, knn_scan, masked_topk
from .pq import PQIndex, adc_tables, build_pq, pq_local_scan, pq_scan

__all__ = ["Index", "IndexOps", "ScanParams", "INDEX_KINDS",
           "register_index", "get_ops",
           "ShardedIVF", "ShardedPQ", "ShardedIVFPQ", "ShardedOPQ",
           "PQQuant", "IVFPQQuant", "OPQIndex", "OPQQuant"]


@dataclasses.dataclass(frozen=True)
class Index:
    """The tagged union: ``kind`` (static aux data) + its array payload.

    Payload types by kind — dense / sharded / frozen-quantizer roles:

      "flat"   scan vectors (N, m)   / row-sharded copy or None / None
      "ivf"    IVFIndex              / ShardedIVF               / centroids
      "pq"     PQIndex               / ShardedPQ                / PQQuant
      "opq"    OPQIndex              / ShardedOPQ               / OPQQuant
      "ivfpq"  IVFPQIndex            / ShardedIVFPQ             / IVFPQQuant
    """
    kind: str
    payload: Any


jax.tree_util.register_dataclass(Index, data_fields=["payload"],
                                 meta_fields=["kind"])


@dataclasses.dataclass(frozen=True)
class ScanParams:
    """Query-time scan knobs (trace-time constants, one bundle).

    ``scan_cap > 0`` switches the ivfpq scan to the nprobe-proportional
    compact variant (``ivfpq_compact_scan``): the candidate gather width
    becomes ``scan_cap`` flat slots sized by actual posting mass instead
    of ``nprobe * max_cell`` padded slots. The engine computes a cap that
    covers any query's probed mass, so results stay bit-identical to the
    padded scan. 0 = padded scan (the default; other kinds ignore it).
    """
    nprobe: int = 8
    backend: str = "jnp"
    lut_dtype: str = "f32"
    scan_cap: int = 0


class ShardedIVF(NamedTuple):
    """IVF payload re-laid for a database-axis mesh (cell-sharded)."""
    centroids: jax.Array    # (nlist, d) replicated
    lists: jax.Array        # (nlist_pad, mc) cell-sharded
    cell_vecs: jax.Array    # (nlist_pad, mc, d) cell-sharded mirror


class ShardedPQ(NamedTuple):
    """Plain-PQ payload re-laid for a database-axis mesh (row-sharded)."""
    codes: jax.Array        # (N_pad, M) row-sharded
    lut_w: jax.Array        # (d, M*K) replicated
    cbnorm: jax.Array       # (M, K) replicated


class ShardedIVFPQ(NamedTuple):
    """IVF-PQ payload re-laid for a database-axis mesh (cell-sharded)."""
    centroids: jax.Array    # (nlist, d) replicated
    lists: jax.Array        # (nlist_pad, mc) cell-sharded
    codes_cell: jax.Array   # (nlist_pad, mc, M) cell-sharded
    bias_cell: jax.Array    # (nlist_pad, mc) cell-sharded
    lut_w: jax.Array        # (d, M*K) replicated
    cbnorm: jax.Array       # (M, K) replicated
    codebooks: jax.Array    # (M, K, dsub) replicated (analytic LUT stats)


class OPQIndex(NamedTuple):
    """OPQ payload: a learned orthogonal rotation of the scan space plus
    plain-PQ state over the rotated rows. Every scan delegates to the PQ
    ADC paths with the query rotated first (rotation is an isometry, so
    delta scans and re-ranks in the unrotated space stay consistent)."""
    rot: jax.Array          # (d, d) learned orthogonal rotation
    codebooks: jax.Array    # (M, K, dsub) over the rotated space
    codes: jax.Array        # (N, M) stored width (uint8 for K <= 256)
    lut_w: jax.Array        # (d, M*K)
    cbnorm: jax.Array       # (M, K)


class ShardedOPQ(NamedTuple):
    """OPQ payload re-laid for a database-axis mesh (row-sharded)."""
    rot: jax.Array          # (d, d) replicated
    codes: jax.Array        # (N_pad, M) row-sharded
    lut_w: jax.Array        # (d, M*K) replicated
    cbnorm: jax.Array       # (M, K) replicated


class PQQuant(NamedTuple):
    """Frozen PQ quantizers (streaming ``FrozenParams`` payload)."""
    codebooks: jax.Array    # (M, K, dsub)
    lut_w: jax.Array        # (d, M*K)
    cbnorm: jax.Array       # (M, K)


class OPQQuant(NamedTuple):
    """Frozen OPQ quantizers (streaming ``FrozenParams`` payload)."""
    rot: jax.Array          # (d, d)
    codebooks: jax.Array    # (M, K, dsub)
    lut_w: jax.Array        # (d, M*K)
    cbnorm: jax.Array       # (M, K)


class IVFPQQuant(NamedTuple):
    """Frozen IVF-PQ quantizers (streaming ``FrozenParams`` payload)."""
    centroids: jax.Array    # (nlist, d)
    codebooks: jax.Array    # (M, K, dsub)
    lut_w: jax.Array        # (d, M*K)
    cbnorm: jax.Array       # (M, K)


@dataclasses.dataclass(frozen=True)
class IndexOps:
    """Everything the serving stack needs to know about one index kind."""
    kind: str
    lossy: bool                       # scan scores approximate the metric
    #                                   (forces over-retrieve + re-rank)
    build: Callable                   # (key, reduced, spec) -> payload
    scan: Callable                    # (state, qr, n_cand, p) -> (d2, cand)
    local_scan: Callable              # (sstate, qr, n_cand, p, axis, slack,
    #                                    live=None) -> (d2, global cand)
    stream_scan: Callable             # (store, frozen, qr, n_cand, live, p)
    #                                    -> (d2, cand)
    shard_payload: Callable           # (state, shards) -> sharded payload
    payload_specs: Callable           # (payload, axis) -> PartitionSpec tree
    store_parts: Callable             # (state, n_cap, cell_slack) ->
    #                                    (store field overrides, quant payload)
    encode_delta: Callable            # (frozen, rows) -> (assign, codes, bias)
    rebuild: Callable                 # (frozen, reduced, shards) -> payload
    stream_base_payload: Callable     # (store, frozen, corpus_owned) ->
    #                                    dense payload over the store
    payload_skeleton: Callable        # (leaf) -> payload-shaped tree of leaf
    #                                    placeholders (snapshot restore)
    quant_skeleton: Callable          # (leaf) -> frozen-quant-shaped tree
    drift_stats: Optional[Callable] = None   # (frozen, rows) -> (B,) squared
    #                                    reconstruction error of scan-space
    #                                    rows under the frozen quantizers
    #                                    (MaintenancePolicy drift signal;
    #                                    None = kind is not quantized)


_REGISTRY: dict = {}


def register_index(ops: IndexOps) -> IndexOps:
    """Install (or replace) the ops entry for ``ops.kind``."""
    _REGISTRY[ops.kind] = ops
    return ops


def get_ops(kind: str) -> IndexOps:
    """Look up the registered ``IndexOps`` for an index kind (the single
    dispatch point of every scan/build/shard/stream site)."""
    try:
        return _REGISTRY[kind]
    except KeyError:
        raise ValueError(
            f"unknown index kind {kind!r}; registered kinds: "
            f"{tuple(_REGISTRY)}") from None


def _pad_dim0(a: Optional[jax.Array], multiple: int, fill=0):
    """Right-pad dim 0 up to a multiple (per-shard-equal blocks)."""
    if a is None:
        return None
    pad = (-a.shape[0]) % multiple
    if not pad:
        return a
    widths = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=fill)


def _pad_rows(a: jax.Array, n_cap: int, fill=0) -> jax.Array:
    """Copy + right-pad dim 0 to the fixed row capacity (fresh buffer)."""
    pad = n_cap - a.shape[0]
    if pad <= 0:
        return jnp.array(a)                    # jnp.array copies
    widths = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=fill)


def _pad_cells(a: jax.Array, slack: int, fill=0) -> jax.Array:
    """Copy + grow the per-cell (dim-1) capacity of a cell-major array."""
    if slack <= 0:
        return jnp.array(a)
    widths = ((0, 0), (0, slack)) + ((0, 0),) * (a.ndim - 2)
    return jnp.pad(a, widths, constant_values=fill)


def _own(a: Optional[jax.Array]) -> Optional[jax.Array]:
    return None if a is None else jnp.array(a)


def _encode_pq(codebooks, x):
    from .segments import encode_pq
    return encode_pq(codebooks, x)


def _ivfpq_encode(centroids, codebooks, x):
    from .segments import ivfpq_encode
    return ivfpq_encode(centroids, codebooks, x)


def _pq_decode(codebooks, codes):
    """Reconstruct rows from PQ codes: (B, M) int32 -> (B, M*dsub) f32."""
    m, kc, dsub = codebooks.shape
    recon = jnp.take_along_axis(
        codebooks[None], codes[:, :, None, None], axis=2)[:, :, 0, :]
    return recon.reshape(codes.shape[0], m * dsub)


# per-kind drift statistics (MaintenancePolicy): squared reconstruction
# error of scan-space rows under the frozen quantizers — how much signal
# the coded scan loses on *today's* data vs the build-time baseline

def _ivf_drift_stats(frozen, rows):
    assign = jnp.argmin(sq_dists(rows, frozen.centroids), axis=1)
    return jnp.sum((rows - frozen.centroids[assign]) ** 2, axis=-1)


def _pq_drift_stats(frozen, rows):
    codes = _encode_pq(frozen.codebooks, rows)
    return jnp.sum((rows - _pq_decode(frozen.codebooks, codes)) ** 2,
                   axis=-1)


def _ivfpq_drift_stats(frozen, rows):
    assign, codes, _ = _ivfpq_encode(frozen.centroids, frozen.codebooks,
                                     rows)
    recon = frozen.centroids[assign] + _pq_decode(frozen.codebooks, codes)
    return jnp.sum((rows - recon) ** 2, axis=-1)


# --- flat: exact scan of the (reduced) vectors -------------------------------

def _flat_build(key, reduced, spec):
    # the payload IS the scan rows; with no Reduce stage this is the corpus
    # array itself (aliasing the sharding/persistence layers preserve)
    return reduced


def _flat_scan(state, qr, n_cand, p):
    return knn_scan(qr, state.index.payload, n_cand)


def _flat_local_scan(sstate, qr, n_cand, p, axis, slack, live=None):
    """Shard-local exact scan over this shard's row block; shard-pad rows
    (global id >= n_real) and — streaming — non-live rows mask to
    (+inf, -1). Distances come from the same ``_sq_dists`` as the
    single-device ``knn_scan`` so the two paths rank identically."""
    x_loc = (sstate.index.payload if sstate.index.payload is not None
             else sstate.corpus)
    n_loc = x_loc.shape[0]
    off = jax.lax.axis_index(axis) * n_loc
    gid = off + jnp.arange(n_loc)
    ok = gid < sstate.n_real
    if live is not None:
        n_cap = live.shape[0]
        ok = ok & live[jnp.clip(gid, 0, n_cap - 1)]
    d2 = jnp.where(ok[None, :], _sq_dists(qr, x_loc), jnp.inf)
    return masked_topk(d2, jnp.broadcast_to(gid[None, :], d2.shape), n_cand)


def _flat_stream_scan(store, frozen, qr, n_cand, live, p):
    scan_rows = store.reduced if store.reduced is not None else store.corpus
    d2 = _sq_dists(qr, scan_rows)
    d2 = jnp.where(live[None, :], d2, jnp.inf)
    n_cap = scan_rows.shape[0]
    ids = jnp.broadcast_to(jnp.arange(n_cap)[None, :], d2.shape)
    return masked_topk(d2, ids, n_cand)


def _flat_shard_payload(state, shards):
    # flat without a Reduce stage scans the corpus itself; don't ship the
    # same rows twice — None routes the local scan to the sharded corpus
    if state.index.payload is state.corpus:
        return None
    return _pad_dim0(state.index.payload, shards)


def _flat_payload_specs(payload, axis):
    return None if payload is None else P(axis)


def _flat_store_parts(state, n_cap, cell_slack):
    if state.proj is None:
        return {}, None            # scan falls back to the corpus row store
    return {"reduced": _pad_rows(state.index.payload, n_cap)}, None


def _flat_encode_delta(frozen, rows):
    return None, None, None


def _flat_rebuild(frozen, reduced, shards):
    return reduced


def _flat_stream_base_payload(store, frozen, corpus_owned):
    return _own(store.reduced) if store.reduced is not None else corpus_owned


register_index(IndexOps(
    kind="flat", lossy=False,
    build=_flat_build, scan=_flat_scan, local_scan=_flat_local_scan,
    stream_scan=_flat_stream_scan, shard_payload=_flat_shard_payload,
    payload_specs=_flat_payload_specs, store_parts=_flat_store_parts,
    encode_delta=_flat_encode_delta, rebuild=_flat_rebuild,
    stream_base_payload=_flat_stream_base_payload,
    payload_skeleton=lambda leaf: leaf,
    quant_skeleton=lambda leaf: None))


# --- ivf: coarse k-means quantizer + probed exact scan -----------------------

def _ivf_build(key, reduced, spec):
    return build_ivf(jax.random.fold_in(key, 1), reduced, spec.coarse.nlist)


def _ivf_scan(state, qr, n_cand, p):
    return ivf_scan(state.index.payload, qr, n_cand, p.nprobe)


def _ivf_local_scan(sstate, qr, n_cand, p, axis, slack, live=None):
    ix = sstate.index.payload
    return ivf_local_scan(ix.centroids, ix.lists, ix.cell_vecs, qr, n_cand,
                          p.nprobe, axis, live=live)


def _ivf_stream_scan(store, frozen, qr, n_cand, live, p):
    scan_rows = store.reduced if store.reduced is not None else store.corpus
    n_cap = scan_rows.shape[0]
    _, cand, _ = probe_cells(frozen.centroids, store.lists, qr, p.nprobe,
                             n_cand)
    ok = (cand >= 0) & live[jnp.clip(cand, 0, n_cap - 1)]
    cv = jnp.take(scan_rows, jnp.maximum(cand, 0), axis=0)
    d2 = jnp.sum((cv - qr[:, None, :]) ** 2, axis=-1)
    return masked_topk(jnp.where(ok, d2, jnp.inf), cand, n_cand)


def _ivf_shard_payload(state, shards):
    ix = state.index.payload
    lists = _pad_dim0(ix.lists, shards, fill=-1)
    return ShardedIVF(centroids=ix.centroids, lists=lists,
                      cell_vecs=cell_vectors(lists, ix.vectors))


def _ivf_payload_specs(payload, axis):
    return ShardedIVF(centroids=P(), lists=P(axis), cell_vecs=P(axis))


def _ivf_store_parts(state, n_cap, cell_slack):
    ix = state.index.payload
    parts = {"lists": _pad_cells(ix.lists, cell_slack, fill=-1)}
    if state.proj is not None:
        parts["reduced"] = _pad_rows(ix.vectors, n_cap)
    return parts, ix.centroids


def _ivf_encode_delta(frozen, rows):
    assign = jnp.argmin(sq_dists(rows, frozen.centroids), axis=1)
    return assign, None, None


def _ivf_rebuild(frozen, reduced, shards):
    from .ivf import posting_lists
    assign = jnp.argmin(sq_dists(reduced, frozen.centroids), axis=1)
    lists = posting_lists(assign, frozen.centroids.shape[0], shards)
    return IVFIndex(centroids=frozen.centroids, lists=lists, vectors=reduced)


def _ivf_stream_base_payload(store, frozen, corpus_owned):
    # vectors need no copy: shard_engine only reads them through
    # cell_vectors(), whose gather materializes fresh buffers
    scan_rows = store.reduced if store.reduced is not None else store.corpus
    return IVFIndex(centroids=frozen.centroids, lists=_own(store.lists),
                    vectors=scan_rows)


register_index(IndexOps(
    kind="ivf", lossy=False,
    build=_ivf_build, scan=_ivf_scan, local_scan=_ivf_local_scan,
    stream_scan=_ivf_stream_scan, shard_payload=_ivf_shard_payload,
    payload_specs=_ivf_payload_specs, store_parts=_ivf_store_parts,
    encode_delta=_ivf_encode_delta, rebuild=_ivf_rebuild,
    stream_base_payload=_ivf_stream_base_payload,
    payload_skeleton=lambda leaf: IVFIndex(
        centroids=leaf, lists=leaf, vectors=leaf),
    quant_skeleton=lambda leaf: leaf,
    drift_stats=_ivf_drift_stats))


# --- pq: product-quantized vectors, fused ADC scan ---------------------------

def _pq_build(key, reduced, spec):
    return build_pq(jax.random.fold_in(key, 2), reduced,
                    spec.code.subspaces, spec.code.centroids)


def _pq_scan(state, qr, n_cand, p):
    return pq_scan(state.index.payload, qr, n_cand, backend=p.backend,
                   lut_dtype=p.lut_dtype)


def _pq_local_scan(sstate, qr, n_cand, p, axis, slack, live=None):
    ix = sstate.index.payload
    return pq_local_scan(ix.lut_w, ix.cbnorm, ix.codes, qr, n_cand,
                         sstate.n_real, axis, backend=p.backend,
                         lut_dtype=p.lut_dtype,
                         slack=slack, live=live)


def _pq_stream_scan(store, frozen, qr, n_cand, live, p):
    from repro.kernels.pq_adc.lut import center_lut
    from repro.kernels.pq_adc.ref import pq_adc_scores_ref
    nq = qr.shape[0]
    m, kc = frozen.cbnorm.shape
    tables = adc_tables(frozen.lut_w, frozen.cbnorm, qr)
    const = jnp.sum(qr * qr, axis=1)
    if p.lut_dtype != "f32":
        tables, offs = center_lut(tables)
        const = const + offs
    scores = (pq_adc_scores_ref(tables, store.codes, p.lut_dtype)
              + const[:, None])
    scores = jnp.where(live[None, :], scores, jnp.inf)
    n_cap = store.codes.shape[0]
    ids = jnp.broadcast_to(jnp.arange(n_cap)[None, :], scores.shape)
    return masked_topk(scores, ids, n_cand)


def _pq_shard_payload(state, shards):
    ix = state.index.payload
    # codes ship at stored width (uint8 for K <= 256); both backends widen
    # in-register, so the sharded copy keeps the 4x memory saving
    return ShardedPQ(
        codes=_pad_dim0(ix.codes, shards),
        lut_w=ix.lut_w, cbnorm=ix.cbnorm)


def _pq_payload_specs(payload, axis):
    return ShardedPQ(codes=P(axis), lut_w=P(), cbnorm=P())


def _pq_store_parts(state, n_cap, cell_slack):
    # no ``reduced`` mirror: the coded base is scanned through its codes,
    # the delta through ``delta_reduced``, the re-rank through ``corpus``
    ix = state.index.payload
    parts = {"codes": _pad_rows(ix.codes, n_cap)}     # stored width (uint8)
    return parts, PQQuant(codebooks=ix.codebooks, lut_w=ix.lut_w,
                          cbnorm=ix.cbnorm)


def _pq_encode_delta(frozen, rows):
    return None, _encode_pq(frozen.codebooks, rows), None


def _pq_rebuild(frozen, reduced, shards):
    code_dt = jnp.uint8 if frozen.codebooks.shape[1] <= 256 else jnp.int32
    return PQIndex(codebooks=frozen.codebooks,
                   codes=_encode_pq(frozen.codebooks,
                                    reduced).astype(code_dt),
                   lut_w=frozen.lut_w, cbnorm=frozen.cbnorm)


def _pq_stream_base_payload(store, frozen, corpus_owned):
    return PQIndex(codebooks=frozen.codebooks, codes=_own(store.codes),
                   lut_w=frozen.lut_w, cbnorm=frozen.cbnorm)


register_index(IndexOps(
    kind="pq", lossy=True,
    build=_pq_build, scan=_pq_scan, local_scan=_pq_local_scan,
    stream_scan=_pq_stream_scan, shard_payload=_pq_shard_payload,
    payload_specs=_pq_payload_specs, store_parts=_pq_store_parts,
    encode_delta=_pq_encode_delta, rebuild=_pq_rebuild,
    stream_base_payload=_pq_stream_base_payload,
    payload_skeleton=lambda leaf: PQIndex(
        codebooks=leaf, codes=leaf, lut_w=leaf, cbnorm=leaf),
    quant_skeleton=lambda leaf: PQQuant(
        codebooks=leaf, lut_w=leaf, cbnorm=leaf),
    drift_stats=_pq_drift_stats))


# --- opq: learned orthogonal rotation + PQ codes -----------------------------
# "Quantization Meets Projection": alternate (1) k-means codebooks on the
# rotated rows with (2) the orthogonal Procrustes solution R = U V^T of
# X^T X_hat — each step can only help the rotated-space quantization, and
# the identity-rotation iterate IS the plain-pq build (same key fold), so
# keeping the lowest-MSE iterate guarantees opq reconstruction error
# <= plain pq at equal code bytes.

_OPQ_ITERS = 3          # Procrustes/assignment alternations after identity


def _opq_pq_view(ix) -> PQIndex:
    """The plain-PQ view of an OPQ payload (scan delegation)."""
    return PQIndex(codebooks=ix.codebooks, codes=ix.codes,
                   lut_w=ix.lut_w, cbnorm=ix.cbnorm)


def _opq_build(key, reduced, spec):
    x = jnp.asarray(reduced, jnp.float32)
    d = x.shape[1]
    rot = jnp.eye(d, dtype=jnp.float32)
    best = None
    best_err = jnp.inf
    # fold 2 on purpose: iterate 0 (rot = I) reproduces _pq_build exactly
    pq_key = jax.random.fold_in(key, 2)
    for _ in range(_OPQ_ITERS + 1):
        xr = x @ rot
        pq = build_pq(pq_key, xr, spec.code.subspaces, spec.code.centroids)
        recon = _pq_decode(pq.codebooks, pq.codes.astype(jnp.int32))
        err = jnp.mean(jnp.sum((xr - recon) ** 2, axis=1))
        if best is None or bool(err < best_err):
            best, best_err = OPQIndex(rot=rot, codebooks=pq.codebooks,
                                      codes=pq.codes, lut_w=pq.lut_w,
                                      cbnorm=pq.cbnorm), err
        u, _, vt = jnp.linalg.svd(x.T @ recon)
        rot = u @ vt
    return best


def _opq_scan(state, qr, n_cand, p):
    ix = state.index.payload
    return pq_scan(_opq_pq_view(ix), qr @ ix.rot, n_cand, backend=p.backend,
                   lut_dtype=p.lut_dtype)


def _opq_local_scan(sstate, qr, n_cand, p, axis, slack, live=None):
    ix = sstate.index.payload
    return pq_local_scan(ix.lut_w, ix.cbnorm, ix.codes, qr @ ix.rot, n_cand,
                         sstate.n_real, axis, backend=p.backend,
                         lut_dtype=p.lut_dtype,
                         slack=slack, live=live)


def _opq_stream_scan(store, frozen, qr, n_cand, live, p):
    # rotate, then the masked plain-PQ ADC scan serves the rotated space
    return _pq_stream_scan(store, frozen, qr @ frozen.quant.payload.rot,
                           n_cand, live, p)


def _opq_shard_payload(state, shards):
    ix = state.index.payload
    return ShardedOPQ(rot=ix.rot, codes=_pad_dim0(ix.codes, shards),
                      lut_w=ix.lut_w, cbnorm=ix.cbnorm)


def _opq_payload_specs(payload, axis):
    return ShardedOPQ(rot=P(), codes=P(axis), lut_w=P(), cbnorm=P())


def _opq_store_parts(state, n_cap, cell_slack):
    ix = state.index.payload
    parts = {"codes": _pad_rows(ix.codes, n_cap)}     # stored width (uint8)
    return parts, OPQQuant(rot=ix.rot, codebooks=ix.codebooks,
                           lut_w=ix.lut_w, cbnorm=ix.cbnorm)


def _opq_encode_delta(frozen, rows):
    rot = frozen.quant.payload.rot
    return None, _encode_pq(frozen.codebooks, rows @ rot), None


def _opq_rebuild(frozen, reduced, shards):
    rot = frozen.quant.payload.rot
    code_dt = jnp.uint8 if frozen.codebooks.shape[1] <= 256 else jnp.int32
    return OPQIndex(rot=rot, codebooks=frozen.codebooks,
                    codes=_encode_pq(frozen.codebooks,
                                     reduced @ rot).astype(code_dt),
                    lut_w=frozen.lut_w, cbnorm=frozen.cbnorm)


def _opq_stream_base_payload(store, frozen, corpus_owned):
    q = frozen.quant.payload
    return OPQIndex(rot=q.rot, codebooks=q.codebooks,
                    codes=_own(store.codes), lut_w=q.lut_w, cbnorm=q.cbnorm)


def _opq_drift_stats(frozen, rows):
    xr = rows @ frozen.quant.payload.rot
    codes = _encode_pq(frozen.codebooks, xr)
    return jnp.sum((xr - _pq_decode(frozen.codebooks, codes)) ** 2, axis=-1)


register_index(IndexOps(
    kind="opq", lossy=True,
    build=_opq_build, scan=_opq_scan, local_scan=_opq_local_scan,
    stream_scan=_opq_stream_scan, shard_payload=_opq_shard_payload,
    payload_specs=_opq_payload_specs, store_parts=_opq_store_parts,
    encode_delta=_opq_encode_delta, rebuild=_opq_rebuild,
    stream_base_payload=_opq_stream_base_payload,
    payload_skeleton=lambda leaf: OPQIndex(
        rot=leaf, codebooks=leaf, codes=leaf, lut_w=leaf, cbnorm=leaf),
    quant_skeleton=lambda leaf: OPQQuant(
        rot=leaf, codebooks=leaf, lut_w=leaf, cbnorm=leaf),
    drift_stats=_opq_drift_stats))


# --- ivfpq: coarse quantizer + PQ-coded residuals ----------------------------

def _ivfpq_build(key, reduced, spec):
    return build_ivfpq(jax.random.fold_in(key, 3), reduced,
                       spec.coarse.nlist, spec.code.subspaces,
                       spec.code.centroids)


def _ivfpq_scan(state, qr, n_cand, p):
    ix = state.index.payload
    if p.scan_cap > 0:
        d2, ids = ivfpq_compact_scan(ix.centroids, ix.lists, ix.codes_cell,
                                     ix.bias_cell, ix.lut_w, ix.cbnorm,
                                     ix.codebooks, qr,
                                     n_cand, p.nprobe, p.scan_cap,
                                     backend=p.backend,
                                     lut_dtype=p.lut_dtype)
        return jnp.sqrt(jnp.maximum(d2, 0.0)), ids
    return ivfpq_scan(ix, qr, n_cand, p.nprobe,
                      backend=p.backend, lut_dtype=p.lut_dtype)


def _ivfpq_local_scan(sstate, qr, n_cand, p, axis, slack, live=None):
    ix = sstate.index.payload
    return ivfpq_local_scan(ix.centroids, ix.lists, ix.codes_cell,
                            ix.bias_cell, ix.lut_w, ix.cbnorm, ix.codebooks,
                            qr, n_cand, p.nprobe, axis, backend=p.backend,
                            lut_dtype=p.lut_dtype,
                            live=live)


def _ivfpq_stream_scan(store, frozen, qr, n_cand, live, p):
    return ivfpq_adc_scan(frozen.centroids, store.lists, store.codes_cell,
                          store.bias_cell, frozen.lut_w, frozen.cbnorm,
                          frozen.codebooks, qr,
                          n_cand, p.nprobe, p.backend,
                          p.lut_dtype, live=live)


def _ivfpq_shard_payload(state, shards):
    ix = state.index.payload
    return ShardedIVFPQ(
        centroids=ix.centroids, lists=_pad_dim0(ix.lists, shards, fill=-1),
        codes_cell=_pad_dim0(ix.codes_cell, shards),
        bias_cell=_pad_dim0(ix.bias_cell, shards),
        lut_w=ix.lut_w, cbnorm=ix.cbnorm, codebooks=ix.codebooks)


def _ivfpq_payload_specs(payload, axis):
    return ShardedIVFPQ(centroids=P(), lists=P(axis), codes_cell=P(axis),
                        bias_cell=P(axis), lut_w=P(), cbnorm=P(),
                        codebooks=P())


def _ivfpq_store_parts(state, n_cap, cell_slack):
    ix = state.index.payload
    parts = {
        "codes": _pad_rows(ix.codes, n_cap),          # stored width (uint8)
        "bias": _pad_rows(ix.bias, n_cap),
        "lists": _pad_cells(ix.lists, cell_slack, fill=-1),
        "codes_cell": _pad_cells(ix.codes_cell, cell_slack),
        "bias_cell": _pad_cells(ix.bias_cell, cell_slack),
    }
    return parts, IVFPQQuant(centroids=ix.centroids, codebooks=ix.codebooks,
                             lut_w=ix.lut_w, cbnorm=ix.cbnorm)


def _ivfpq_encode_delta(frozen, rows):
    return _ivfpq_encode(frozen.centroids, frozen.codebooks, rows)


def _ivfpq_rebuild(frozen, reduced, shards):
    from .ivf import posting_lists
    assign, codes, bias = _ivfpq_encode(frozen.centroids, frozen.codebooks,
                                        reduced)
    lists = posting_lists(assign, frozen.centroids.shape[0], shards)
    lid = jnp.maximum(lists, 0)
    code_dt = jnp.uint8 if frozen.codebooks.shape[1] <= 256 else jnp.int32
    recon = frozen.centroids[assign] + _pq_decode(frozen.codebooks, codes)
    rerr = jnp.sqrt(jnp.sum((reduced - recon) ** 2, axis=1))
    return IVFPQIndex(
        centroids=frozen.centroids, lists=lists,
        codebooks=frozen.codebooks, codes=codes.astype(code_dt), bias=bias,
        rerr=rerr.astype(jnp.float32),
        codes_cell=codes[lid].astype(code_dt),
        bias_cell=jnp.where(lists >= 0, bias[lid], 0.0).astype(jnp.float32),
        lut_w=frozen.lut_w, cbnorm=frozen.cbnorm)


def _ivfpq_stream_base_payload(store, frozen, corpus_owned):
    # rerr stays zero here: the re-rank pre-filter never engages on
    # streaming engines (the scan must stay zero-recompile under churn),
    # and a zero bound only ever *keeps* candidates — never unsafe
    return IVFPQIndex(
        centroids=frozen.centroids, lists=_own(store.lists),
        codebooks=frozen.codebooks, codes=_own(store.codes),
        bias=_own(store.bias), rerr=jnp.zeros_like(store.bias),
        codes_cell=_own(store.codes_cell),
        bias_cell=_own(store.bias_cell),
        lut_w=frozen.lut_w, cbnorm=frozen.cbnorm)


register_index(IndexOps(
    kind="ivfpq", lossy=True,
    build=_ivfpq_build, scan=_ivfpq_scan, local_scan=_ivfpq_local_scan,
    stream_scan=_ivfpq_stream_scan, shard_payload=_ivfpq_shard_payload,
    payload_specs=_ivfpq_payload_specs, store_parts=_ivfpq_store_parts,
    encode_delta=_ivfpq_encode_delta, rebuild=_ivfpq_rebuild,
    stream_base_payload=_ivfpq_stream_base_payload,
    payload_skeleton=lambda leaf: IVFPQIndex(
        centroids=leaf, lists=leaf, codebooks=leaf, codes=leaf, bias=leaf,
        rerr=leaf, codes_cell=leaf, bias_cell=leaf, lut_w=leaf, cbnorm=leaf),
    quant_skeleton=lambda leaf: IVFPQQuant(
        centroids=leaf, codebooks=leaf, lut_w=leaf, cbnorm=leaf),
    drift_stats=_ivfpq_drift_stats))


# derived from the registry: one register_index() call covers every scan /
# shard / stream / persistence dispatch site. (Exposing a new kind through
# the ServeConfig/spec-string front end additionally needs a stage mapping
# in repro.search.spec — the grammar can only express these stage
# combinations — but engines over a registered kind serve through
# search_fn/EngineState directly.)
INDEX_KINDS = tuple(_REGISTRY)
