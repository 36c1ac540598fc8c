"""Batched vector-search serving engine: a functional one-program core.

Pipeline (DESIGN.md §2): corpus -> [fit MPAD on a sample] -> reduce corpus ->
[build an index over reduced vectors] -> serve batched queries:
reduce query -> index probe/scan in reduced space -> exact re-rank of the C
candidates in the original space -> top-k.

The reduced-space scan is where the paper's win lands: score FLOPs and corpus
bytes scale with m instead of n, and the re-rank restores exactness on the
short candidate list.

The composable API
------------------

The pipeline is declared by an ``IndexSpec`` (``repro.search.spec``) —
``Reduce -> Coarse -> Code -> Rerank`` stages with a string grammar
(``"qpad32>ivf64x8>pq8x256:i8"``) — and lowered onto a **tagged index
union** (``repro.search.registry.Index``): one ``kind`` tag + stage
payload instead of four mutually-exclusive Optional fields. Every scan
site dispatches through the per-kind ``IndexOps`` registry, so adding an
index kind is one registry entry. The legacy flat ``ServeConfig`` keeps
working (it lowers onto a spec via ``spec_from_config``, which also
rejects dead knobs).

Lifecycle::

    eng = build_engine(corpus, "qpad32>ivf256x8>pq16x256:i8")   # build
    eng.shard(mesh)                   # optional: partition over a mesh
    eng.streaming(StreamConfig(...))  # optional: enable the write path
    eng.save(dir)                     # snapshot: spec + arrays
    eng = load_engine(dir)            # restore (optionally onto a mesh)

Serving architecture
--------------------

The engine is split into a **pytree of arrays** and a **pure function**:

* ``EngineState`` — an immutable pytree holding the re-rank corpus, the
  (optional) MPAD projection, and the built index as the tagged union.
  Being a pytree, it shards, donates, and serialises like any other jax
  state; the ``kind`` tag is pytree aux data, so it is static under jit
  and keys compile caches through the treedef.
* ``search_fn(state, queries, k, *, nprobe, rerank, backend, lut_dtype)``
  — the whole query pipeline (project -> probe -> ADC/flat scan ->
  dedup'd masked re-rank gather -> final top-k) as one traceable function.
  Jitted, it compiles to a **single XLA program**: no Python dispatch or
  host syncs between stages.

``SearchEngine`` is a thin stateful wrapper: it builds ``EngineState`` once,
owns a per-engine ``jax.jit(search_fn)`` whose cache is keyed by
``(index kind + knobs, k, query bucket)``, and pads incoming query batches
up to power-of-two buckets (floored at ``ServeConfig.query_bucket``) so
ragged traffic reuses compilations — batch sizes {9, 33, 64} all run the
one program compiled for bucket 64. Batches of at most
``ServeConfig.small_batch`` (default 8) take their own power-of-two bucket
instead of the floor, so a single query runs a compute-proportional scan
rather than a 64-wide one (the small-batch latency cliff).
``SearchEngine.compile_count`` exposes the cache size for regression tests.

Sharded serving
---------------

``shard_engine(state, mesh, axis="data")`` (``repro.parallel.engine``)
partitions the state pytree along the **database axis** of a device mesh:
corpus rows and the per-kind sharded payload (row-sharded flat
vectors/PQ codes, cell-sharded IVF/IVF-PQ posting structures; projection,
centroids, and codebook factorizations replicated — see
``IndexOps.shard_payload``). ``sharded_search_fn`` then runs the same
fused pipeline under ``shard_map``: each shard probes (replicated math —
identical on every shard), scans only the rows/cells it owns, keeps a
local top-n_cand with **global** row ids via its shard offset, and the
shards finish with an ``all_gather`` + global top-k merge and a masked
exact re-rank in which each shard gathers only the winning candidates it
owns (``psum``-free: a ``pmin`` combines the per-shard masked distances).
The merge keeps the exact candidate set of the single-device program, so
sharded and single-device serving return identical neighbors; the
single-device path itself is untouched. The jit cache keys on the mesh
(shape + devices), so resizing the fleet recompiles exactly once per
shape.

Streaming (mutable) serving
---------------------------

``engine.streaming(StreamConfig(...))`` (or the declarative
``ServeConfig(stream=...)``) enables the write path: the built index
becomes the frozen **base** layer of a ``repro.search.segments.StreamStore``
(fixed row capacity + posting-list pad slack + tombstone bitmap) with a
fixed-capacity exact-scan **delta segment** on top.
``SearchEngine.upsert/delete/compact`` are pure donated-jit programs over
that store — no recompiles per write — and ``search`` routes through
``repro.search.stream.stream_search_fn`` (or its sharded twin: base
sharded, delta/tombstones replicated).

Durability & maintenance (``repro.search.durability``):
``engine.durable(dir)`` opens a write-ahead log that every mutation
appends to before it runs, so ``load_engine(dir)`` replays the tail on
top of the newest snapshot and recovers the exact pre-crash store;
``StreamConfig(background_compact=True)`` double-buffers compaction
(searches keep serving the old store until the atomic swap); a
``MaintenancePolicy`` (``StreamConfig(policy=PolicyConfig(...))``)
watches tombstone density, capacity headroom, and quantizer drift and
triggers ``vacuum``/grow/``rebuild_quantizers`` — every decision logged
to the WAL for deterministic replay. ``engine.metrics()`` surfaces the
counters; ``engine.tracing()`` (``repro.search.tracing``) adds latency
histograms, slow-query capture, and online recall estimation on top. The
host work of every search, write and compaction runs under named program
spans (``qpad.search``, ``qpad.upsert``, ``qpad.compact.fold``, ...) that a
``jax.profiler`` trace records beside the device ops.

Index kinds (``IndexSpec.kind`` / ``ServeConfig.index``):

  "flat"   exact scan of the (reduced) vectors
  "ivf"    k-means coarse quantizer, probe nprobe cells, exact cell scan
  "pq"     product-quantized vectors, fused ADC scan
  "ivfpq"  coarse quantizer + PQ-coded residuals, probed ADC scan — the
           production memory-hierarchy composition

The ``Code`` stage's ``lut_dtype`` ("f32" | "bf16" | "int8") quantizes the
per-query ADC lookup tables of the pq/ivfpq scans (see
``repro.kernels.pq_adc.lut``).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import MPADConfig
from repro.kernels.pq_adc.lut import LUT_DTYPES, lut_error_bound
from .durability.wal import (RT_COMPACT, RT_DELETE, RT_POLICY, RT_UPSERT,
                             encode_delete, encode_policy, encode_upsert)
from .reducers import Reducer, fit_reducer, reduce_vectors
from .registry import INDEX_KINDS, Index, ScanParams, get_ops
from .segments import StreamConfig
from .spec import IndexSpec, parse_spec, spec_from_config
from .tracing import span

__all__ = ["ServeConfig", "SearchEngine", "EngineState",
           "ShardedEngineState", "StreamConfig", "search_fn",
           "sharded_search_fn", "exact_rerank", "INDEX_KINDS",
           "build_engine", "config_from_spec"]

_ADC_BACKENDS = ("jnp", "kernel")
_SEARCH_STATICS = ("k", "nprobe", "rerank", "backend", "lut_dtype",
                   "scan_cap", "prefilter")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """The flat (legacy) engine config: pipeline knobs + runtime knobs.

    The pipeline part lowers onto an ``IndexSpec`` (``spec_from_config``)
    — which is also where cross-knob validation happens: ``nprobe`` may
    not exceed ``nlist``, and knobs whose stage is absent from the
    selected pipeline (e.g. ``nlist`` under ``index="pq"``) are rejected
    instead of silently ignored. Prefer building engines from a spec
    (``build_engine(corpus, "qpad32>ivf64x8>pq8x256:i8")``); construct a
    ``ServeConfig`` directly when you need the runtime knobs too.
    """
    target_dim: Optional[int] = None     # None = no reduction (full-dim exact)
    reducer: str = "qpad"                # Reduce-stage kind (REDUCER_KINDS):
    #                                      "qpad" | "pca" | "mlp" | registered
    rerank: int = 64                     # candidates re-ranked in original space
    index: str = "flat"                  # one of INDEX_KINDS
    nlist: int = 64                      # ivf/ivfpq: coarse cells
    nprobe: int = 8                      # ivf/ivfpq: cells probed per query
    pq_subspaces: int = 8                # pq/ivfpq: code bytes per vector
    pq_centroids: int = 256              # pq/ivfpq: codebook size per subspace
    pq_backend: str = "jnp"              # ADC scoring: "jnp" | "kernel"
    #                                      (the kernel compiles on a TPU and
    #                                      is interpreted elsewhere)
    lut_dtype: str = "f32"               # ADC LUT precision: f32 | bf16 | int8
    query_bucket: int = 64               # min padded query-batch size; ragged
    #                                      batches round up to powers of two
    small_batch: int = 8                 # batches <= this take their own
    #                                      power-of-two bucket instead of the
    #                                      query_bucket floor (0 disables)
    compact_batch: int = 64              # ivfpq read-only engines: buckets
    #                                      <= this take the nprobe-
    #                                      proportional compact scan when the
    #                                      posting-mass bound beats the padded
    #                                      gather; returned ids stay
    #                                      bit-identical (0 disables)
    prefilter_batch: int = 0             # ivfpq read-only engines without a
    #                                      projection: buckets <= this shrink
    #                                      the exact re-rank to certified ADC
    #                                      survivors. Ids stay bit-identical,
    #                                      but it only pays when the PQ
    #                                      reconstruction error is small next
    #                                      to neighbor gaps (else the bound
    #                                      admits everyone and the full-width
    #                                      fallback runs anyway), so it is
    #                                      opt-in (0 disables, the default)
    mpad: Optional[MPADConfig] = None    # defaults derived from target_dim
    fit_sample: int = 2048               # rows used to fit the projection
    seed: int = 0
    stream: Optional[StreamConfig] = None  # enable the mutable write path
    #                                        (delta segment + tombstones +
    #                                        compaction; see search/stream.py)
    # removed boolean index spec (PR-1 deprecation cycle complete): any
    # value raises with a pointer to the spec grammar
    use_ivf: Optional[bool] = None
    use_pq: Optional[bool] = None

    def __post_init__(self):
        if self.use_ivf is not None or self.use_pq is not None:
            raise ValueError(
                "ServeConfig(use_ivf=/use_pq=) was removed after its "
                "deprecation cycle; select the pipeline with "
                "ServeConfig(index='ivf'|'pq'|'ivfpq') or an index-spec "
                "string such as 'qpad32>ivf64x8>pq8x256:i8' "
                "(repro.search.parse_spec)")
        if self.index not in INDEX_KINDS:
            raise ValueError(
                f"unknown index kind {self.index!r}; expected one of "
                f"{INDEX_KINDS}")
        if self.pq_backend not in _ADC_BACKENDS:
            raise ValueError(
                f"unknown pq_backend {self.pq_backend!r}; expected one of "
                f"{_ADC_BACKENDS}")
        if self.lut_dtype not in LUT_DTYPES:
            raise ValueError(
                f"unknown lut_dtype {self.lut_dtype!r}; expected one of "
                f"{LUT_DTYPES}")
        if self.query_bucket < 1:
            raise ValueError("query_bucket must be >= 1")
        if self.small_batch < 0:
            raise ValueError("small_batch must be >= 0 (0 disables the "
                             "small-batch bucket floor path)")
        if self.compact_batch < 0:
            raise ValueError("compact_batch must be >= 0 (0 disables the "
                             "compact small-batch scan)")
        if self.prefilter_batch < 0:
            raise ValueError("prefilter_batch must be >= 0 (0 disables the "
                             "re-rank candidate pre-filter)")
        if (self.stream is not None and self.index in ("pq", "opq")
                and self.pq_backend == "kernel"):
            raise ValueError(
                f"streaming index={self.index!r} needs pq_backend='jnp': "
                "the shared-codes Pallas kernel has no masked entry point "
                "for an arbitrary tombstone bitmap (use index='ivfpq' for "
                "a kernel-backed streaming ADC scan)")
        # stage-level validation: lower onto the pipeline spec (rejects
        # nprobe > nlist, dead knobs, bad stage values)
        self.to_spec()

    def to_spec(self) -> IndexSpec:
        """Lower this config onto its pipeline spec (validating)."""
        return spec_from_config(self)


def config_from_spec(spec, **runtime) -> ServeConfig:
    """Lower an ``IndexSpec`` (or spec string) onto a ``ServeConfig``.

    ``runtime`` forwards the engine knobs a pipeline spec does not carry
    (``query_bucket``, ``small_batch``, ``mpad``, ``fit_sample``,
    ``seed``, ``stream``). Round-trips with
    ``ServeConfig.to_spec``.
    """
    if isinstance(spec, str):
        spec = parse_spec(spec)
    if not isinstance(spec, IndexSpec):
        raise TypeError(f"IndexSpec or spec string expected, got "
                        f"{type(spec).__name__}")
    kw = dict(index=spec.kind, rerank=spec.rerank.n)
    if spec.reduce is not None:
        kw["target_dim"] = spec.reduce.m
        kw["reducer"] = spec.reduce.kind
    if spec.coarse is not None:
        kw.update(nlist=spec.coarse.nlist, nprobe=spec.coarse.nprobe)
    if spec.code is not None:
        kw.update(pq_subspaces=spec.code.subspaces,
                  pq_centroids=spec.code.centroids,
                  lut_dtype=spec.code.lut_dtype,
                  pq_backend=spec.code.backend)
    kw.update(runtime)
    return ServeConfig(**kw)


def as_serve_config(config) -> ServeConfig:
    """Accept a ServeConfig, an IndexSpec, or a spec string everywhere a
    config is expected."""
    if isinstance(config, ServeConfig):
        return config
    if isinstance(config, (str, IndexSpec)):
        return config_from_spec(config)
    raise TypeError(
        "expected a ServeConfig, an IndexSpec, or a spec string like "
        f"'qpad32>ivf64x8>pq8x256:i8'; got {type(config).__name__}")


class EngineState(NamedTuple):
    """Everything ``search_fn`` needs, as one immutable pytree.

    ``index`` is the tagged union: ``index.kind`` selects the registered
    ``IndexOps`` (static under jit — it rides the treedef), ``index.payload``
    is that kind's built arrays. ``corpus`` is the original-space row store
    for the exact re-rank; ``proj`` the (optional) fitted Reduce stage —
    a ``repro.search.reducers.Reducer`` tagged union whose ``kind`` is
    pytree metadata, exactly like ``index.kind``.
    """
    corpus: jax.Array                              # (N, D) re-rank space
    proj: Optional[Reducer]                        # fitted Reduce stage
    index: Index                                   # tagged union payload


class ShardedEngineState(NamedTuple):
    """``EngineState`` re-laid-out for data-parallel serving on a mesh.

    ``corpus`` is padded to a per-shard-equal shape and sharded along dim
    0; ``index`` holds the kind's **sharded** payload (see
    ``IndexOps.shard_payload`` — row- or cell-sharded database leaves,
    replicated quantizers); the reducer params replicate. Built by
    ``repro.parallel.engine.shard_engine``; consumed by
    ``sharded_search_fn``. ``n_real`` is the unpadded corpus size — rows
    at or beyond it are shard padding, masked out of every scan.
    """
    corpus: jax.Array                              # (N_pad, D) row-sharded
    proj: Optional[Reducer]                        # replicated reducer params
    n_real: jax.Array                              # () int32 replicated
    index: Index                                   # kind + sharded payload


def _dedupe_candidates(cand: jax.Array):
    """Collapse duplicate candidate ids to -1: sort (pads sort first) +
    neighbor compare. Returns (cand sorted/deduped, valid mask). Shared by
    the single-device and sharded re-ranks — their parity depends on running
    the identical prologue."""
    cand = jnp.sort(cand, axis=1)                        # pads (-1) sort first
    dup = jnp.concatenate(
        [jnp.zeros_like(cand[:, :1], bool), cand[:, 1:] == cand[:, :-1]],
        axis=1)
    cand = jnp.where(dup, -1, cand)
    return cand, cand >= 0


def exact_rerank(queries: jax.Array, corpus: jax.Array, cand: jax.Array,
                 k: int):
    """Re-score candidate ids in the original space; top-k of the survivors.

    ``cand`` (Q, C) may contain -1 pads and duplicate ids (over-retrieval
    across probes): duplicates are collapsed to -1 first (sort + neighbor
    compare), then a single masked gather pulls each surviving row once and
    pads/dups are held out of the top-k with +inf.
    """
    cand, valid = _dedupe_candidates(cand)
    cv = jnp.take(corpus, jnp.where(valid, cand, 0), axis=0)   # (Q, C, D)
    d2 = jnp.sum((cv - queries[:, None, :]) ** 2, axis=-1)
    d2 = jnp.where(valid, d2, jnp.inf)
    neg, sel = jax.lax.top_k(-d2, k)
    ids = jnp.take_along_axis(cand, sel, axis=1)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), ids


def _prefiltered_rerank(state: EngineState, queries: jax.Array,
                        qr: jax.Array, d_scan: jax.Array, cand: jax.Array,
                        k: int, r_s: int, lut_dtype: str):
    """Exact re-rank behind the in-scan candidate pre-filter.

    The ivfpq ADC scan already scored every candidate; with no projection
    the scan space IS the re-rank space, so per-candidate bounds on the
    true distance d = ||q - x|| follow from the stored per-row PQ
    reconstruction error ``rerr = ||x - x̂||`` (triangle inequality) plus
    the LUT quantization bound b (``lut_error_bound``; 0 for f32):

        LB = max(0, sqrt(max(d2 - b, 0)) - rerr) <= d
        UB = sqrt(d2 + b) + rerr                 >= d

    The k-th smallest UB is a certified threshold W >= d_(k): any
    candidate with LB > W has d > d_(k) strictly and cannot be a true
    top-k member (ties at d_(k) always satisfy LB <= d = d_(k) <= W, so
    the tie-break pool is preserved and the returned IDS are
    bit-identical; distances can wiggle by reduction-order ULPs since the
    narrower gather vectorizes the feature sum differently).
    When every query's survivor count fits the static width ``r_s``, the
    survivors are stably compacted left and the exact gather runs r_s
    wide instead of rerank wide — the stage that dominates small batches.
    Otherwise (rare: W is loose only when rerr is large) the full-width
    re-rank runs unchanged.
    """
    ix = state.index.payload
    n = state.corpus.shape[0]
    valid = cand >= 0
    rerr = ix.rerr[jnp.clip(cand, 0, n - 1)]                # (Q, C)
    if lut_dtype != "f32":
        # same matmul + (int8) scale the scan ran on the same operands
        # (``ivfpq_lut_stats``) — XLA CSEs the repeats, and the bound is
        # computed on exactly the grid the scan quantized onto: the raw
        # tables for bf16 (relative rounding, no centering), the analytic
        # centered scale for int8
        from .ivfpq import ivfpq_lut_stats
        from .pq import adc_tables
        tables = adc_tables(ix.lut_w, ix.cbnorm, qr)
        scale = None
        if lut_dtype == "int8":
            _, scale = ivfpq_lut_stats(ix.codebooks, ix.cbnorm, qr,
                                       lut_dtype)
        b = lut_error_bound(tables, lut_dtype, scale)[:, None]    # (Q, 1)
    else:
        b = jnp.zeros((1, 1), jnp.float32)
    d2 = jnp.square(d_scan)
    ub = jnp.sqrt(jnp.maximum(d2 + b, 0.0)) + rerr
    lb = jnp.maximum(jnp.sqrt(jnp.maximum(d2 - b, 0.0)) - rerr, 0.0)
    ub = jnp.where(valid, ub, jnp.inf)
    negk, _ = jax.lax.top_k(-ub, k)
    w = -negk[:, -1:]                                       # (Q, 1) = W
    # relative slack absorbs the sqrt/square round-trips; slack only KEEPS
    # extra candidates, never drops more — safety is one-sided
    keep = valid & (lb <= w + 1e-3 * (1.0 + jnp.abs(w)))

    def _tight(_):
        order = jnp.argsort(~keep, axis=1, stable=True)[:, :r_s]
        cc = jnp.take_along_axis(cand, order, axis=1)
        kk = jnp.take_along_axis(keep, order, axis=1)
        return exact_rerank(queries, state.corpus,
                            jnp.where(kk, cc, -1), k)

    def _full(_):
        return exact_rerank(queries, state.corpus, cand, k)

    fits = jnp.max(jnp.sum(keep.astype(jnp.int32), axis=1)) <= r_s
    return jax.lax.cond(fits, _tight, _full, None)


def _check_rerank_budget(approximate: bool, rerank: int, k: int):
    if approximate and rerank < k:
        raise ValueError(
            f"k={k} exceeds the re-rank budget rerank={rerank} on an "
            "approximate pipeline (reduction and/or PQ codes): the exact "
            "re-rank could only return rerank candidates. Raise the "
            f"Rerank stage (e.g. spec '...>rr{k}') or lower k.")


def search_fn(state: EngineState, queries: jax.Array, k: int, *,
              nprobe: int = 8, rerank: int = 64, backend: str = "jnp",
              lut_dtype: str = "f32", scan_cap: int = 0, prefilter: int = 0):
    """The entire query pipeline as one pure traceable function.

    project -> probe/scan (dispatched on ``state.index.kind`` through the
    ops registry) -> exact re-rank -> top-k. Jitted
    (``jax.jit(search_fn, static_argnames=_SEARCH_STATICS)``) this is
    a single XLA program; the index kind is pytree aux data, so it keys
    the compile cache without being an argument. Every per-query op is
    row-independent, so padded query rows never perturb real results.
    Returns (dists (Q,k), ids (Q,k)); distances in the original space when
    re-ranking is active, else in the serving (reduced) space.

    ``scan_cap > 0`` (ivfpq) sizes the candidate gather by actual posting
    mass instead of ``nprobe * max_cell`` (``ivfpq_compact_scan``);
    ``prefilter > 0`` (ivfpq, no projection) shrinks the exact re-rank to
    that many certified survivors (``_prefiltered_rerank``). Both are
    engaged by ``SearchEngine`` for small buckets and keep the returned
    ids bit-identical to the defaults (the compact scan keeps distances
    bit-identical too; the pre-filter's narrower re-rank gather can move
    distances by reduction-order ULPs).
    """
    ops = get_ops(state.index.kind)
    queries = jnp.asarray(queries, jnp.float32)
    # named_scope annotations label the stage boundaries inside the fused
    # program for jax.profiler / Perfetto timelines (see
    # repro.search.tracing); they are free at run time
    with jax.named_scope("qpad.project"):
        qr = reduce_vectors(state.proj, queries)
    # lossy scoring (reduction and/or PQ codes) -> over-retrieve + re-rank
    approximate = state.proj is not None or ops.lossy
    _check_rerank_budget(approximate, rerank, k)
    n_cand = rerank if approximate else k
    p = ScanParams(nprobe=nprobe, backend=backend, lut_dtype=lut_dtype,
                   scan_cap=scan_cap)
    with jax.named_scope("qpad.scan"):
        d_scan, cand = ops.scan(state, qr, n_cand, p)
    if prefilter > 0:
        if state.index.kind != "ivfpq" or state.proj is not None:
            raise ValueError(
                "prefilter needs an ivfpq index with no Reduce stage: the "
                "certified distance bounds require the scan space to be "
                "the re-rank space")
        if prefilter < n_cand:
            with jax.named_scope("qpad.rerank"):
                return _prefiltered_rerank(state, queries, qr, d_scan,
                                           cand, k, prefilter, lut_dtype)
    with jax.named_scope("qpad.rerank"):
        return exact_rerank(queries, state.corpus, cand, k)


# --- sharded serving (shard_map over a database-axis mesh) -------------------

def _sharded_rerank(queries: jax.Array, corpus_loc: jax.Array,
                    cand: jax.Array, k: int, axis: str):
    """``exact_rerank`` with the corpus row-sharded: the same sort + dedupe
    runs replicated, then each shard gathers and scores only the candidates
    it owns and a ``pmin`` over the mesh axis assembles the full exact
    distance row (every candidate is owned by exactly one shard) — only the
    k winners' rows are ever touched on any device."""
    cand, valid = _dedupe_candidates(cand)
    n_loc = corpus_loc.shape[0]
    off = jax.lax.axis_index(axis) * n_loc
    local = cand - off
    own = valid & (local >= 0) & (local < n_loc)
    cv = jnp.take(corpus_loc, jnp.clip(local, 0, n_loc - 1), axis=0)
    d2 = jnp.sum((cv - queries[:, None, :]) ** 2, axis=-1)
    d2 = jnp.where(own, d2, jnp.inf)
    d2 = jax.lax.pmin(d2, axis)                          # (Q, C) replicated
    neg, sel = jax.lax.top_k(-d2, k)
    ids = jnp.take_along_axis(cand, sel, axis=1)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), ids


def _sharded_core(sstate: ShardedEngineState, queries: jax.Array, *, k: int,
                  nprobe: int, rerank: int, backend: str,
                  lut_dtype: str, axis: str, slack: int,
                  scan_cap: int = 0, prefilter: int = 0):
    """The shard_map body: the full per-shard pipeline + distributed merge."""
    if scan_cap or prefilter:
        raise ValueError(
            "scan_cap/prefilter are single-device read-only fast paths: "
            "the compact scan sizes on the unsharded posting mass and the "
            "pre-filter bounds assume the full candidate row — leave both "
            "0 on the sharded path")
    ops = get_ops(sstate.index.kind)
    queries = jnp.asarray(queries, jnp.float32)
    with jax.named_scope("qpad.project"):
        qr = reduce_vectors(sstate.proj, queries)
    approximate = sstate.proj is not None or ops.lossy
    _check_rerank_budget(approximate, rerank, k)
    n_cand = rerank if approximate else k
    p = ScanParams(nprobe=nprobe, backend=backend, lut_dtype=lut_dtype)
    with jax.named_scope("qpad.scan"):
        d2, cand = ops.local_scan(sstate, qr, n_cand, p, axis, slack)
    # distributed merge: every shard's local top-n_cand is a superset of the
    # global top-n_cand members it owns, so the merged set equals the
    # single-device candidate set exactly
    with jax.named_scope("qpad.merge"):
        d2g = jax.lax.all_gather(d2, axis, axis=1, tiled=True)  # (Q, S*n_cand)
        idg = jax.lax.all_gather(cand, axis, axis=1, tiled=True)
        neg, sel = jax.lax.top_k(-d2g, n_cand)
        merged = jnp.take_along_axis(idg, sel, axis=1)
        merged = jnp.where(jnp.isneginf(neg), -1, merged)
    with jax.named_scope("qpad.rerank"):
        return _sharded_rerank(queries, sstate.corpus, merged, k, axis)


def sharded_search_fn(sstate: ShardedEngineState, queries: jax.Array, k: int,
                      *, mesh: Mesh, axis: str = "data",
                      nprobe: int = 8, rerank: int = 64, backend: str = "jnp",
                      lut_dtype: str = "f32",
                      scan_cap: int = 0, prefilter: int = 0):
    """``search_fn`` partitioned over the ``axis`` of ``mesh``.

    Same contract and — by construction of the distributed merge — the same
    results as the single-device ``search_fn`` on the unsharded state.
    Jit with ``mesh``/``axis`` static (``Mesh`` hashes by shape + devices,
    which is exactly what the compile cache must key on).
    """
    from repro.parallel.sharding import engine_state_specs
    specs = engine_state_specs(sstate, axis)
    core = functools.partial(
        _sharded_core, k=k, nprobe=nprobe, rerank=rerank,
        backend=backend, lut_dtype=lut_dtype, axis=axis,
        slack=mesh.shape[axis] - 1, scan_cap=scan_cap, prefilter=prefilter)
    f = jax.shard_map(core, mesh=mesh, in_specs=(specs, P()),
                      out_specs=(P(), P()), check_vma=False)
    return f(sstate, queries)


def _build_record(spec: IndexSpec, payload, n: int) -> dict:
    """What the blocked build did, for the metrics' ``build.*`` section:
    the rows, the rows of one build block and the blocks of a pass (the
    coarse k-means's where there is one, else the PQ codebooks'), and the
    cells' fill."""
    from .ivf import block_rows
    rec = {"rows": n, "block_rows": None, "blocks": None, "nlist": None,
           "max_cell": None}
    tile = None
    if spec.coarse is not None:
        tile = spec.coarse.nlist
        rec.update(nlist=spec.coarse.nlist,
                   max_cell=int(payload.lists.shape[1]))
    elif spec.code is not None:
        tile = min(spec.code.centroids, n)
    if tile is not None:
        rec["block_rows"] = block_rows(n, tile)
        rec["blocks"] = -(-n // rec["block_rows"])
    return rec


def _bucket(nq: int, floor: int, small: int = 0) -> int:
    """Smallest power-of-two >= nq, floored at ``floor`` — except batches of
    at most ``small``, which take their own power-of-two bucket so tiny
    batches run a compute-proportional program instead of padding to the
    floor (the small-batch latency cliff; ``small=0`` disables)."""
    pow2 = 1 << max(nq - 1, 0).bit_length()
    if 0 < nq <= small:
        return pow2
    return max(floor, pow2)


class SearchEngine:
    """Build once over a corpus; serve batched k-NN queries.

    Thin wrapper over the functional core: construction builds
    ``self.state`` (an ``EngineState``), ``search`` pads the batch to its
    bucket and calls the engine-owned jitted ``search_fn``. The config may
    be a ``ServeConfig``, an ``IndexSpec``, or a spec string. Mutating
    ``self.config`` (e.g. ``dataclasses.replace(..., nprobe=16)``) is
    supported — knob changes re-key the jit cache, not the state.

    Lifecycle methods: ``shard(mesh)`` partitions the state over a device
    mesh, ``streaming(StreamConfig(...))`` enables the mutable write path
    (``upsert``/``delete``/``compact``), ``save(dir)`` snapshots spec +
    arrays (restore with ``repro.search.load_engine``).
    """

    def __init__(self, corpus: jax.Array, config=ServeConfig()):
        config = as_serve_config(config)
        spec = config.to_spec()
        corpus_in = corpus
        corpus = jnp.asarray(corpus, jnp.float32)
        # when the caller's array passes through unconverted, it stays
        # caller-owned: shard(donate=True) must not delete it
        self._user_corpus = corpus if corpus is corpus_in else None
        n, dim = corpus.shape
        key = jax.random.key(config.seed)
        with span("qpad.build"):
            if spec.reduce is not None:
                mcfg = config.mpad
                if mcfg is None and spec.reduce.kind == "qpad":
                    mcfg = MPADConfig(
                        m=spec.reduce.m, b=80.0, alpha=25.0, iters=48,
                        seed=config.seed)
                with span("qpad.build.fit"):
                    sample = corpus
                    if config.fit_sample < n:
                        rows = jax.random.choice(
                            key, n, (config.fit_sample,), replace=False)
                        sample = corpus[rows]
                    proj: Optional[Reducer] = fit_reducer(
                        spec.reduce.kind, key, sample, spec.reduce.m, mcfg)
                with span("qpad.build.reduce"):
                    reduced = reduce_vectors(proj, corpus)
            else:
                proj = None
                reduced = corpus
            payload = get_ops(config.index).build(key, reduced, spec)
        state = EngineState(corpus=corpus, proj=proj,
                            index=Index(config.index, payload))
        self._attach(config, state, proj)
        self._build = _build_record(spec, payload, n)

    # --- lifecycle --------------------------------------------------------

    def _attach(self, config: ServeConfig, state: Optional[EngineState],
                reducer: Optional[Reducer], store=None, frozen=None):
        """Wire a built (or restored) state into a serving engine: jit
        programs, compile caches, counters. The shared tail of ``__init__``
        and the snapshot-restore constructors."""
        self._user_corpus = getattr(self, "_user_corpus", None)
        self._build = None           # how __init__ built the index (the
        #                              metrics' build.* section); None on
        #                              engines restored from a snapshot
        self.config = config
        self.reducer = reducer
        self.state: Optional[EngineState] = state
        self.last_bucket: Optional[int] = None   # padded size of the last
        #                                          served batch (shape pin
        #                                          for latency tests)
        self._scan_caps: dict = {}   # nprobe -> compact-scan gather width
        #                              (host-side, cached: one posting-mass
        #                              sync per nprobe per engine)
        self.sharded_state: Optional[ShardedEngineState] = None
        self._mesh: Optional[Mesh] = None
        self._shard_axis = "data"
        self._sharded_program = None
        # engine-owned jit: a fresh closure gives this engine its own
        # compilation cache (jax shares caches for identical function
        # objects), keyed by (statics, query bucket)
        def _engine_search_fn(state, queries, k, **kw):
            return search_fn(state, queries, k, **kw)
        self._program = jax.jit(_engine_search_fn,
                                static_argnames=_SEARCH_STATICS)
        self.store, self.frozen = store, frozen  # streaming (write) state
        self._stream_sharded_base = None
        self._stream_program = self._stream_sharded_program = None
        self._upsert_program = self._delete_program = None
        self._compact_program = None
        self.grow_count = 0          # compaction-overflow regrowths (rare;
        #                              each one is a recompile point)
        self._delta_used = 0         # conservative host mirror of the delta
        #                              fill (overwrites counted as appends)
        # durability + maintenance (repro.search.durability)
        self.crash_hook = None       # optional callable(point_name) fired at
        #                              named lifecycle points ("wal_appended",
        #                              "compact_begin", "compact_task",
        #                              "compact_swap", "compact_done",
        #                              "vacuum", "rebuild") — plug
        #                              FailureInjector.maybe_fail in for
        #                              crash drills, or block in it to
        #                              schedule background compaction
        self._replaying = False      # WAL replay in flight: appends and
        #                              policy auto-decisions disabled
        self._wal = None             # durability.wal.Wal once durable()
        self._durability = None      # its DurabilityConfig
        self._durable_dir = None     # snapshot+wal directory
        self._replayed = 0           # records applied by recovery
        # replication (repro.search.durability.replication)
        self._role = "primary"       # "follower" engines tail a shipped
        #                              WAL and reject local writes
        self._applied_seq = -1       # last WAL seq reflected in the store
        #                              (snapshot position + replay/catch-up)
        self._repl_catch_ups = 0     # catch_up passes completed
        self._repl_records = 0       # shipped records applied
        self._repl_source_tail = -1  # source tail at the last catch_up
        self._repl_last_catch_up_ts = None   # wall clock of the last
        #                              catch_up pass (staleness gauge)
        self._repl_caught_up_ts = None       # wall clock of the last
        #                              catch_up that drained the source
        #                              (replication.lag_seconds)
        # observability (repro.search.tracing): None until tracing() —
        # the serve path takes zero extra work without a tracer
        self._tracer = None
        # incremental snapshots (repro.search.snapshot)
        self._base_ref = None        # the chain this engine can extend:
        #                              {dir, ckpt, wal_seq, chain} of the
        #                              newest full snapshot + incrementals
        self._base_dirty = False     # base arrays rewritten since the base
        #                              snapshot (compact/vacuum/rebuild/
        #                              grow): the next save must be full
        self._snap_counters = {"full": 0, "incremental": 0,
                               "last_bytes": 0, "chain_depth": 0}
        self._policy = None          # MaintenancePolicy (streaming engines)
        self._policy_active = False  # auto-decisions only when the user
        #                              configured StreamConfig.policy
        self._compact_future = None  # pending background compaction
        self._compact_executor = None
        self._compact_tail = []      # writes logged during the pending
        #                              compaction, re-applied at the swap
        self._tail_rows = 0
        self._counters = {"compactions": 0, "swaps": 0, "vacuums": 0,
                          "rebuilds": 0, "policy_grows": 0}
        if store is not None:        # restored mid-delta snapshot
            self._delta_used = int(store.delta_count)
            self._stream_programs()
            self._stream_policy_init()
        elif config.stream is not None:
            self._init_stream()

    @classmethod
    def _restore(cls, config: ServeConfig, *, state=None, store=None,
                 frozen=None) -> "SearchEngine":
        """Construct an engine around already-built arrays (snapshot
        restore): no MPAD refit, no index retrain. Exactly one of
        ``state`` (read-only) or ``store``+``frozen`` (streaming) is
        given; see ``repro.search.snapshot``."""
        eng = object.__new__(cls)
        eng._user_corpus = None
        proj = state.proj if state is not None else frozen.proj
        eng._attach(config, state, proj, store=store, frozen=frozen)
        return eng

    @property
    def spec(self) -> IndexSpec:
        """The pipeline spec this engine serves (lowered from the current
        config, so query-time knob mutations are reflected)."""
        return self.config.to_spec()

    def save(self, directory: str, incremental: bool = False) -> str:
        """Snapshot the engine (spec + config + arrays) into ``directory``;
        restore with ``repro.search.load_engine``. Covers read-only and
        streaming engines (the delta segment and tombstones are saved
        as-is, so a mid-delta snapshot restores mid-delta). Returns the
        checkpoint path.

        ``incremental=True`` persists only what changes between
        snapshots of a streaming engine — the delta segment, tombstone
        bitmap, id maps and WAL position — against the newest *full*
        snapshot already in ``directory`` (chained manifests;
        ``load_engine`` resolves the chain). Checkpoint cost stops
        scaling with base size, and the result doubles as the cheap
        re-seed artifact for followers. Requires a prior full ``save``
        to the same directory and a base untouched since (after a
        compaction / vacuum / rebuild / grow the next save must be
        full); incoherent calls raise with the fix spelled out."""
        from .snapshot import save_engine
        return save_engine(self, directory, incremental=incremental)

    @property
    def compile_count(self) -> int:
        """Number of compiled (statics, bucket) variants this engine holds
        (single-device + sharded + streaming read/write programs)."""
        progs = [self._program, self._sharded_program,
                 self._stream_program, self._stream_sharded_program,
                 self._upsert_program, self._delete_program,
                 self._compact_program]
        try:
            return sum(int(p._cache_size()) for p in progs if p is not None)
        except AttributeError as e:     # private jax hook; fail loudly if
            raise RuntimeError(          # an unpinned jax drops it
                "jax no longer exposes PjitFunction._cache_size(); "
                "SearchEngine.compile_count needs a replacement hook"
            ) from e

    # --- streaming (mutable) serving -------------------------------------

    def streaming(self, config: Optional[StreamConfig] = None
                  ) -> "SearchEngine":
        """Enable the mutable write path on a built engine: the dense
        index becomes the frozen base of a ``StreamStore`` with a delta
        segment + tombstones on top, and ``upsert``/``delete``/``compact``
        come alive. One-way and idempotent-hostile by design: call once,
        after build and before ``shard``. Returns ``self`` for chaining.
        (The declarative ``ServeConfig(stream=...)`` route does this at
        construction.)
        """
        if self.store is not None:
            raise RuntimeError(
                "this engine is already streaming; re-configure by "
                "rebuilding or load_engine from a snapshot")
        if self.sharded_state is not None:
            raise RuntimeError(
                "enable streaming BEFORE shard(): the store takes over "
                "the dense arrays, which would leave the placed sharded "
                "state stale (or, on a zero-copy placement, deleted) — "
                "rebuild, call streaming(...), then shard(mesh)")
        if self.state is None:
            raise RuntimeError(
                "the dense EngineState is gone (shard(donate=True)); "
                "streaming needs the dense arrays — rebuild the engine "
                "or load_engine from a snapshot")
        # replace() re-runs config validation (e.g. pq+kernel streaming)
        self.config = dataclasses.replace(
            self.config, stream=config or StreamConfig())
        self._init_stream()
        return self

    def _require_stream(self):
        if self.store is None:
            raise RuntimeError(
                "this engine is read-only; enable the write path with "
                "engine.streaming(StreamConfig(...)) or "
                "ServeConfig(stream=StreamConfig(...))")
        if self._role == "follower" and not self._replaying:
            from .durability.replication import ReplicationError
            raise ReplicationError(
                "this engine is a follower: its store is a replica of a "
                "primary's WAL and local writes would fork the history. "
                "Write to the primary and catch_up, or re-open the "
                "snapshot without role='follower' to promote it.")

    def _init_stream(self):
        from .segments import make_mutable
        self.store, self.frozen = make_mutable(self.state,
                                               self.config.stream)
        # the store owns fresh (capacity-padded) copies of every database
        # leaf, so the dense EngineState duplicates them — release the
        # duplicated buffers (the frozen quantizers and any caller-owned
        # corpus stay shared/alive) instead of holding 2x forever
        hold = {id(leaf) for leaf in jax.tree_util.tree_leaves(self.frozen)}
        if self._user_corpus is not None:
            hold.add(id(self._user_corpus))
        dense = {id(a): a for a in jax.tree_util.tree_leaves(self.state)}
        for leaf in dense.values():
            if id(leaf) not in hold and not leaf.is_deleted():
                leaf.delete()
        self.state = None
        self._stream_programs()
        self._stream_policy_init()

    def _stream_policy_init(self):
        """Create the MaintenancePolicy and (when the user configured one)
        seed its drift baseline: mean encode error of a sample of the base
        rows under the freshly trained frozen quantizers."""
        from .durability.policy import MaintenancePolicy
        scfg = self.config.stream
        self._policy = MaintenancePolicy(scfg.policy)
        self._policy_active = scfg.policy is not None
        if not self._policy_active:
            return
        ops = get_ops(self.config.index)
        n = int(self.store.n_rows)
        if ops.drift_stats is None or n == 0:
            return
        from .segments import _project
        rows = self.store.corpus[:min(n, 1024)]
        err = ops.drift_stats(self.frozen,
                              _project(self.frozen.proj, rows))
        self._policy.observe_build_error(float(jnp.mean(err)))

    def _stream_programs(self):
        """Jit the streaming read/write programs (fresh closures: per-engine
        compile caches, same as ``_program``). The write programs donate
        the store, so the ``.at[]`` updates alias the input buffers
        instead of copying the row store per write."""
        from .segments import compact_fn, delete_fn, upsert_fn
        from .stream import sharded_stream_search_fn, stream_search_fn

        def _engine_stream_fn(store, frozen, queries, k, **kw):
            return stream_search_fn(store, frozen, queries, k, **kw)
        self._stream_program = jax.jit(_engine_stream_fn,
                                       static_argnames=_SEARCH_STATICS)

        def _engine_upsert(store, frozen, ids, vectors):
            return upsert_fn(store, frozen, ids, vectors)
        self._upsert_program = jax.jit(_engine_upsert, donate_argnums=(0,))

        def _engine_delete(store, ids):
            return delete_fn(store, ids)
        self._delete_program = jax.jit(_engine_delete, donate_argnums=(0,))

        def _engine_compact(store, frozen):
            return compact_fn(store, frozen)
        self._compact_program = jax.jit(_engine_compact, donate_argnums=(0,))

        def _engine_stream_sharded(sbase, repl, queries, k, **kw):
            return sharded_stream_search_fn(sbase, repl, queries, k, **kw)
        self._stream_sharded_program = jax.jit(
            _engine_stream_sharded,
            static_argnames=_SEARCH_STATICS + ("mesh", "axis"))

    def _crash(self, point: str):
        if self.crash_hook is not None:
            self.crash_hook(point)

    def _wal_append(self, rtype: int, payload: bytes = b"", *,
                    wait: bool = True):
        """Log one record *before* the mutation it describes (no-op when
        the engine is not durable or is replaying its own log).
        ``wait=False`` defers the group-commit durability wait — a
        multi-chunk write batch waits once at the end
        (``_wal_wait_durable``) instead of once per chunk."""
        if self._wal is None or self._replaying:
            return
        with span("qpad.wal.append"):
            self._wal.append(rtype, payload, wait=wait)
        self._crash("wal_appended")

    def _wal_wait_durable(self):
        """Batch-end durability point for ``wait=False`` appends (no-op
        outside group-commit mode)."""
        if self._wal is not None and not self._replaying:
            self._wal.wait_durable()

    def _pad_write(self, ids, vectors=None):
        """Pad a write batch up to its ``write_bucket`` bucket (-1 id
        pads are no-ops in the write programs)."""
        ids = jnp.asarray(ids, jnp.int32).reshape(-1)
        n = ids.shape[0]
        bucket = _bucket(n, self.config.stream.write_bucket)
        if bucket != n:
            ids = jnp.pad(ids, (0, bucket - n), constant_values=-1)
        if vectors is None:
            return ids, None
        vectors = jnp.asarray(vectors, jnp.float32).reshape(n, -1)
        if bucket != n:
            vectors = jnp.pad(vectors, ((0, bucket - n), (0, 0)))
        return ids, vectors

    def _compact_point(self) -> int:
        """Delta fill (rows) that triggers auto-compaction."""
        scfg = self.config.stream
        fill = scfg.compact_threshold
        if self._policy is not None and self._policy.config.delta_fill:
            fill = self._policy.config.delta_fill
        return max(1, min(scfg.delta_capacity,
                          int(fill * scfg.delta_capacity)))

    def _ensure_delta_room(self, chunk: int, cap: int, point: int):
        """Pre-write maintenance: compact (blocking or double-buffered)
        so the next ``chunk`` delta rows fit."""
        if self._compact_future is not None:
            if (self._delta_used + chunk > cap
                    or self._tail_rows + chunk > point):
                self.finish_compact()
            else:
                return      # the pending fold reclaims the delta at the swap
        if self._delta_used + chunk > point:
            if (self._compact_future is None
                    and self.config.stream.background_compact
                    and self._delta_used + chunk <= cap):
                self.begin_compact()
            else:
                self.compact()

    def upsert(self, ids: jax.Array, vectors: jax.Array):
        """Insert or overwrite rows by external id (ids (B,), vectors
        (B, D)). Pure in-place delta appends — no recompilation (batches
        pad to ``StreamConfig.write_bucket``-floored power-of-two buckets)
        and no index rebuild; the delta auto-compacts into the base at
        ``compact_threshold`` (double-buffered off-thread under
        ``StreamConfig(background_compact=True)``). On a durable engine
        each chunk is WAL-logged before it lands. Returns ``self``.
        """
        self._require_stream()
        with span("qpad.upsert"):
            self._poll_compaction()
            ids = np.asarray(ids, np.int32).reshape(-1)
            vectors = np.asarray(vectors, np.float32).reshape(
                ids.shape[0], -1)
            cap = self.config.stream.delta_capacity
            point = self._compact_point()
            b = 0
            while b < ids.shape[0]:
                chunk = min(ids.shape[0] - b, point)
                if not self._replaying:
                    self._ensure_delta_room(chunk, cap, point)
                cid, cv = ids[b:b + chunk], vectors[b:b + chunk]
                self._wal_append(RT_UPSERT, encode_upsert(cid, cv),
                                 wait=False)
                if self._compact_future is not None:
                    # the pending fold donated a pre-begin copy; replay
                    # this write onto the folded store at the swap
                    self._compact_tail.append(
                        ("upsert", cid.copy(), cv.copy()))
                    self._tail_rows += chunk
                pid, pv = self._pad_write(cid, cv)
                # dropped stays 0 by construction (the chunking above
                # never exceeds the compact point), so it is not synced
                # to host here
                with span("qpad.upsert.launch"):
                    self.store, _ = self._upsert_program(
                        self.store, self.frozen, pid, pv)
                self._delta_used += chunk
                b += chunk
            self._wal_wait_durable()     # one group-commit wait per batch
        return self

    def delete(self, ids: jax.Array):
        """Delete rows by external id: tombstone base copies, punch delta
        holes. Absent ids are no-ops. WAL-logged on a durable engine;
        with a configured ``StreamConfig.policy``, a dense-enough
        tombstone bitmap triggers ``vacuum`` (the reclaim path deletes
        alone never had). Returns ``self``."""
        self._require_stream()
        with span("qpad.delete"):
            self._poll_compaction()
            ids = np.asarray(ids, np.int32).reshape(-1)
            self._wal_append(RT_DELETE, encode_delete(ids))
            if self._compact_future is not None:
                self._compact_tail.append(("delete", ids.copy(), None))
            pid, _ = self._pad_write(ids)
            with span("qpad.delete.launch"):
                self.store = self._delete_program(self.store, pid)
            if not self._replaying and self._policy_active:
                dead = int(jnp.sum(self.store.dead))
                decision = self._policy.decide_delete(
                    dead=dead, allocated=int(self.store.n_rows))
                if decision.kind == "vacuum":
                    self.vacuum()
        return self

    # --- compaction (blocking and double-buffered) ------------------------

    def _run_compact(self, store):
        """The fold + grow-retry loop over ``store`` (donated). Returns
        (folded store, grows)."""
        from .segments import grow_store
        scfg = self.config.stream
        store, dropped = self._compact_program(store, self.frozen)
        grows = 0
        while int(dropped):
            # one delta's worth of cell slack covers the worst case (every
            # delta row landing in one cell), so a single grow suffices
            store = grow_store(store,
                               row_extra=4 * scfg.delta_capacity,
                               cell_extra=scfg.delta_capacity)
            grows += 1
            store, dropped = self._compact_program(store, self.frozen)
        return store, grows

    def _compact_task(self, store):
        """The background fold, on the ``qpad-compact`` thread."""
        with span("qpad.compact.fold"):
            self._crash("compact_task")
            return self._run_compact(store)

    def _install_compacted(self, store, grows, tail, tail_rows):
        """Re-apply the tail writes recorded during the fold, then swap
        the folded store in atomically (a single reference assignment —
        searches observe the old store or the new one, never a mix)."""
        for kind, tids, tvecs in tail:
            pid, pv = self._pad_write(tids, tvecs)
            if kind == "upsert":
                store, _ = self._upsert_program(store, self.frozen, pid, pv)
            else:
                store = self._delete_program(store, pid)
        self._crash("compact_swap")
        self.store = store
        self._delta_used = tail_rows
        self._base_dirty = True      # the fold rewrote the base arrays
        self.grow_count += grows
        self._counters["compactions"] += 1
        self._counters["swaps"] += 1
        if self._stream_sharded_base is not None:
            self._shard_stream_base()        # re-lay the (grown) base out
        self._crash("compact_done")
        if not self._replaying:
            self._post_compact_maintenance()

    def compact(self):
        """Fold the delta segment into the base index (re-coding against
        the frozen quantizers — shapes and compiled programs survive),
        blocking until the swap. A pending ``begin_compact`` is finished
        first. On a durable engine the COMPACT barrier is logged before
        the fold, so recovery redoes an interrupted compaction.

        If the append would overflow the pre-allocated row capacity or a
        posting cell's slack, the store grows host-side and the compaction
        retries: correct, but a recompile point (``grow_count`` ticks) —
        size ``StreamConfig.row_capacity``/``cell_slack`` to avoid it.
        Returns ``self``.
        """
        self._require_stream()
        if self._compact_future is not None:
            self.finish_compact()
        self._observe_drift()
        self._wal_append(RT_COMPACT)
        self._crash("compact_begin")
        store, grows = self._run_compact(self.store)
        self._install_compacted(store, grows, (), 0)
        return self

    def begin_compact(self):
        """Start a double-buffered compaction: fold a *copy* of the store
        on a worker thread while searches (and further writes) keep
        serving the live store; ``finish_compact`` (or the automatic poll
        at the next search/write once the fold is done) re-applies the
        writes that landed meanwhile and swaps atomically. No-op if a
        compaction is already pending. Returns ``self``."""
        self._require_stream()
        if self._compact_future is not None:
            return self
        self._observe_drift()
        self._wal_append(RT_COMPACT)
        self._crash("compact_begin")
        with span("qpad.compact.begin"):                 # the double buffer
            snapshot = jax.tree.map(jnp.array, self.store)
        self._compact_tail = []
        self._tail_rows = 0
        if self._compact_executor is None:
            from concurrent.futures import ThreadPoolExecutor
            self._compact_executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="qpad-compact")
        self._compact_future = self._compact_executor.submit(
            self._compact_task, snapshot)
        return self

    def finish_compact(self):
        """Complete a pending ``begin_compact``: wait for the fold,
        re-apply the tail writes, swap. No-op without one. Returns
        ``self``."""
        self._require_stream()
        fut = self._compact_future
        if fut is None:
            return self
        try:
            store, grows = fut.result()
        finally:
            self._compact_future = None
        tail, self._compact_tail = self._compact_tail, []
        rows, self._tail_rows = self._tail_rows, 0
        with span("qpad.compact.install"):
            self._install_compacted(store, grows, tail, rows)
        return self

    def _poll_compaction(self):
        """Swap in a background compaction that has finished folding —
        called at every search/write entry, so the swap needs no timer."""
        fut = self._compact_future
        if fut is not None and fut.done():
            self.finish_compact()

    # --- maintenance policy ----------------------------------------------

    def _lut_noise_floor(self) -> float:
        """The smallest drift worth acting on: below the LUT
        quantization error bound the coded scan could not express the
        difference anyway."""
        cb = self.frozen.cbnorm if self.frozen is not None else None
        if cb is None or self.config.index not in ("pq", "opq", "ivfpq"):
            return 0.0
        from repro.kernels.pq_adc.lut import lut_error_bound
        return float(lut_error_bound(cb[None], self.config.lut_dtype)[0])

    def _observe_drift(self):
        """Feed the encode error of the delta rows about to be folded
        into the policy's drift estimate."""
        if not self._policy_active or self._replaying:
            return
        ops = get_ops(self.config.index)
        if ops.drift_stats is None:
            return
        store = self.store
        rows = (store.delta_reduced if store.delta_reduced is not None
                else store.delta_vectors)
        cap = store.delta_ids.shape[0]
        alive = (jnp.arange(cap) < store.delta_count) & (store.delta_ids >= 0)
        n = int(jnp.sum(alive))
        if n == 0:
            return
        err = ops.drift_stats(self.frozen, rows)
        self._policy.observe_encode_error(
            float(jnp.sum(jnp.where(alive, err, 0.0))) / n, n)

    def _post_compact_maintenance(self):
        """Run the post-compaction policy decision (grow / rebuild)."""
        if not self._policy_active:
            return
        scfg = self.config.stream
        free = int(self.store.corpus.shape[0]) - int(self.store.n_rows)
        decision = self._policy.decide_post_compact(
            free_rows=free, delta_capacity=scfg.delta_capacity,
            noise_floor=self._lut_noise_floor())
        if decision.kind == "grow":
            from .segments import grow_store
            self._wal_append(RT_POLICY, encode_policy(
                {"decision": "grow", **decision.params}))
            self.store = grow_store(self.store, **decision.params)
            self._base_dirty = True
            self._counters["policy_grows"] += 1
            if self._stream_sharded_base is not None:
                self._shard_stream_base()
        elif decision.kind == "rebuild":
            self.rebuild_quantizers()

    def _gather_live(self):
        """Host-side gather of every live row (base survivors in row
        order, then live delta rows in slot order — a deterministic
        order, so WAL replay of vacuum/rebuild reproduces the store
        exactly). Returns (vectors (L, D) f32, external ids (L,) i32)."""
        store = self.store
        row_ids = np.asarray(store.row_ids)
        live = (row_ids >= 0) & ~np.asarray(store.dead)
        cap = store.delta_ids.shape[0]
        dids = np.asarray(store.delta_ids)
        alive = (np.arange(cap) < int(store.delta_count)) & (dids >= 0)
        vectors = np.concatenate([np.asarray(store.corpus)[live],
                                  np.asarray(store.delta_vectors)[alive]])
        ext = np.concatenate([row_ids[live], dids[alive]]).astype(np.int32)
        return vectors, ext

    def vacuum(self):
        """Reclaim tombstoned rows: rewrite the base over the live rows
        (delta folded in) against the FROZEN quantizers — no retraining.
        The masked scan stops paying for dead rows; shapes shrink back to
        ``StreamConfig`` capacities, so the write programs recompile once
        (rare by construction: the tombstone-density policy gates it).
        WAL-logged as a policy decision. Returns ``self``."""
        self._require_stream()
        if self._compact_future is not None:
            self.finish_compact()
        self._wal_append(RT_POLICY, encode_policy({"decision": "vacuum"}))
        self._crash("vacuum")
        self._do_vacuum()
        return self

    def _do_vacuum(self):
        from .segments import make_mutable, rebuild_state
        vectors, ext = self._gather_live()
        state = rebuild_state(self.frozen, vectors)
        store, frozen = make_mutable(state, self.config.stream)
        store = store._replace(row_ids=store.row_ids.at[:len(ext)].set(
            jnp.asarray(ext)))
        self.store, self.frozen = store, frozen
        self._delta_used = 0
        self._base_dirty = True
        self._counters["vacuums"] += 1
        if self._stream_sharded_base is not None:
            self._shard_stream_base()

    def rebuild_quantizers(self, seed: Optional[int] = None):
        """Full quantizer retrain over the live rows through the ordinary
        build path (new MPAD fit + index train, fresh drift baseline),
        keeping external ids. The drift-policy escape hatch for when the
        frozen quantizers no longer fit the data; every compiled program
        re-keys (new constants), so this is the expensive, rare op the
        whole streaming design exists to avoid needing often. WAL-logged
        with its seed for deterministic replay. Returns ``self``."""
        self._require_stream()
        if self._compact_future is not None:
            self.finish_compact()
        if seed is None:
            seed = self.config.seed + 1 + self._counters["rebuilds"]
        self._wal_append(RT_POLICY, encode_policy(
            {"decision": "rebuild", "seed": int(seed)}))
        self._crash("rebuild")
        self._do_rebuild(int(seed))
        return self

    def _do_rebuild(self, seed: int):
        vectors, ext = self._gather_live()
        cfg = dataclasses.replace(self.config, seed=seed)
        fresh = SearchEngine(vectors, cfg)
        store = fresh.store._replace(
            row_ids=fresh.store.row_ids.at[:len(ext)].set(jnp.asarray(ext)))
        decisions = self._policy.decisions if self._policy else {}
        self.config = cfg
        self.store, self.frozen = store, fresh.frozen
        self.reducer = fresh.reducer
        self._policy = fresh._policy         # fresh drift baseline
        if self._policy is not None:
            self._policy.decisions = decisions
        self._delta_used = 0
        self._base_dirty = True
        self._counters["rebuilds"] += 1
        self._stream_programs()              # new constants: re-key caches
        if self._stream_sharded_base is not None:
            self._shard_stream_base()

    def _apply_policy_record(self, decision: dict):
        """Replay one RT_POLICY record (recovery path)."""
        kind = decision.get("decision")
        if kind == "vacuum":
            self._do_vacuum()
        elif kind == "grow":
            from .segments import grow_store
            self.store = grow_store(
                self.store, row_extra=int(decision["row_extra"]),
                cell_extra=int(decision["cell_extra"]))
            self._base_dirty = True
            self._counters["policy_grows"] += 1
        elif kind == "rebuild":
            self._do_rebuild(int(decision["seed"]))
        else:
            raise ValueError(f"unknown policy decision {decision!r}")

    # --- durability -------------------------------------------------------

    def durable(self, directory: str, config=None):
        """Make this streaming engine durable: open a write-ahead log
        under ``directory`` and take the initial durable snapshot there.
        From here on every ``upsert``/``delete``/``compact``/policy
        decision is logged *before* it mutates the store, ``save()`` to
        the same directory marks + truncates the log, and
        ``load_engine(directory)`` recovers the exact live store after a
        crash (snapshot + WAL-tail replay). ``config`` is a
        ``repro.search.durability.DurabilityConfig`` (fsync mode, segment
        size). Returns ``self``."""
        from .durability.wal import DurabilityConfig, Wal
        self._require_stream()
        if self._wal is not None:
            raise RuntimeError(
                "this engine is already durable; one WAL per engine "
                f"(directory {self._durable_dir!r})")
        config = config or DurabilityConfig()
        if config.role == "follower" or self._role == "follower":
            raise ValueError(
                "durable(role='follower') is incoherent: a follower "
                "tails a primary's shipped WAL and never owns a local "
                "one (local writes on a follower would fork the "
                "history). Seed a follower with load_engine(snapshot, "
                "role='follower') + durability.replication.catch_up; "
                "use role='primary' (the default) for a writable node.")
        os.makedirs(directory, exist_ok=True)
        self._wal = Wal(os.path.join(directory, "wal"), config)
        self._durability = config
        self._durable_dir = os.path.abspath(directory)
        self.save(directory)                 # the initial durable snapshot
        return self

    def metrics(self):
        """The engine's typed metrics snapshot: an
        ``repro.search.metrics.EngineMetrics`` of frozen dataclasses
        with stable dotted names (``wal.records``, ``stream.fill``,
        ``compact.pending``, ``policy.drift_ema``,
        ``replication.follower_lag_seq``, ...). This is the
        observability surface — benches, regression gates and the
        launcher's ``--metrics-port`` endpoint consume it; sections that
        do not apply to this engine are ``None``."""
        from .metrics import collect_metrics
        return collect_metrics(self)

    def tracing(self, config=None, **knobs) -> "SearchEngine":
        """Attach request-level observability (``repro.search.tracing``):
        latency histograms into ``metrics().latency``, optional
        slow-query capture (``slow_query_ms=T``) and shadow-exact recall
        estimation (``recall_every=N``). The program spans need no
        tracer: profile with ``repro.search.jax_profile(dir)``.

        Pass a ``TraceConfig`` or its fields as keyword knobs; calling
        with no arguments attaches the cheap production default
        (end-to-end histograms only). ``tracing(None)`` with an explicit
        ``config=None`` and no knobs re-attaches defaults too; detach
        with ``engine.tracer = None`` via the attribute. Returns ``self``
        for chaining."""
        from .tracing import TraceConfig, Tracer
        if config is None:
            config = TraceConfig(**knobs)
        elif knobs:
            config = dataclasses.replace(config, **knobs)
        self._tracer = Tracer(config)
        return self

    @property
    def tracer(self):
        """The attached ``Tracer`` (None when tracing is off)."""
        return self._tracer

    @tracer.setter
    def tracer(self, value):
        self._tracer = value

    def _shard_stream_base(self):
        from repro.parallel.engine import shard_stream
        self._stream_sharded_base = shard_stream(
            self.store, self.frozen, self._mesh, axis=self._shard_axis)

    # --- sharding ---------------------------------------------------------

    def shard(self, mesh: Optional[Mesh] = None, axis: str = "data",
              donate: bool = False):
        """Partition the engine over the ``axis`` of ``mesh`` (default: the
        mesh activated by ``repro.parallel.context.mesh_context``).

        Subsequent ``search`` calls route through ``sharded_search_fn`` —
        same results, database split across the mesh devices. Returns
        ``self`` for chaining. Re-call with a different mesh to re-shard.

        ``donate=True`` releases the dense single-device buffers once the
        sharded copy is placed (no 2x database memory): re-sharding then
        raises, and switching back via ``sharded_state = None`` is no
        longer possible. With the default ``donate=False`` both copies
        stay live — fine for dry-runs, 2x memory at real scale.

        On a streaming engine the **base** shards and the delta segment /
        tombstones stay replicated (writes keep working; ``compact()``
        re-lays the base out). Donation is refused there: the dense store
        is the write path.
        """
        if mesh is None:
            from repro.parallel.context import require_mesh
            mesh = require_mesh("SearchEngine.shard()")
        self._mesh, self._shard_axis = mesh, axis
        if self.store is not None:
            if donate:
                raise ValueError(
                    "donate=True is not supported on a streaming engine: "
                    "the dense StreamStore backs upsert/delete/compact")
            if self._compact_future is not None:
                self.finish_compact()    # lay out the post-fold base, once
            self._shard_stream_base()
            return self
        if self.state is None:
            raise RuntimeError(
                "the dense EngineState is gone: its buffers were released "
                "by shard(donate=True) — rebuild the engine (or "
                "load_engine from a snapshot) to re-shard")
        from repro.parallel.engine import shard_engine
        keep = (self._user_corpus,) if self._user_corpus is not None else ()
        self.sharded_state = shard_engine(self.state, mesh,
                                          axis=axis, donate=donate,
                                          keep=keep)
        if donate:
            self.state = None
            if self.reducer is not None:
                # the dense reducer params were donated; point the public
                # reducer at the replicated sharded copies so
                # eng.reducer(x) keeps working
                self.reducer = self.sharded_state.proj
        if self._sharded_program is None:
            def _engine_sharded_fn(sstate, queries, k, **kw):
                return sharded_search_fn(sstate, queries, k, **kw)
            self._sharded_program = jax.jit(
                _engine_sharded_fn,
                static_argnames=_SEARCH_STATICS + ("mesh", "axis"))
        return self

    def _scan_cap(self, nprobe: int) -> int:
        """Compact-scan gather width for this engine at ``nprobe``: the
        worst-case probed posting mass (sum of the ``nprobe`` largest cell
        fills), rounded up to a lane multiple — so the capped gather can
        NEVER truncate a query's candidates and results stay bit-identical
        to the padded scan. Returns 0 (disabled) unless the bound beats the
        padded ``nprobe * max_cell`` gather by a wide margin: each compact
        slot costs ~1.5x a padded slot (the prefix-sum slot mapping and the
        2D cell/slot gathers), so a cap must remove well over a third of
        the slots to win — in practice that means a few outlier-huge cells,
        the regime the cap exists for, not mild skew. Host-side and cached:
        the posting-mass sync runs once per (engine, nprobe)."""
        cap = self._scan_caps.get(nprobe)
        if cap is None:
            lists = self.state.index.payload.lists
            lens = np.asarray(jnp.sum(lists >= 0, axis=1))
            top = np.sort(lens)[-nprobe:]
            cap = -(-int(top.sum()) // 128) * 128
            if cap * 8 >= nprobe * lists.shape[1] * 5:
                cap = 0
            self._scan_caps[nprobe] = cap
        return cap

    def search(self, queries: jax.Array, k: int):
        """Returns (dists (Q,k), ids (Q,k)); distances in the original space
        when re-ranking is active, else in the serving (reduced) space.

        One device program per call: the batch is zero-padded up to its
        power-of-two bucket (>= ``config.query_bucket``) so every batch size
        in a bucket reuses the same compilation, then sliced back to Q rows.
        """
        with span("qpad.search"):
            with span("qpad.search.prepare"):
                queries, nq, kw = self._prepare(queries, k)
                # tracing: one perf_counter read when a tracer is attached
                # and active; with no tracer the serve path is the old one
                tracer = self._tracer
                t0 = (time.perf_counter()
                      if tracer is not None and tracer.active else None)
                if self.store is not None:
                    self._poll_compaction()  # swap in a finished fold
            with span("qpad.search.launch"):
                if self.store is not None:
                    if self._stream_sharded_base is not None:
                        from .stream import replica_from_store
                        repl = replica_from_store(self.store)
                        d, ids = self._stream_sharded_program(
                            self._stream_sharded_base, repl, queries, k,
                            mesh=self._mesh, axis=self._shard_axis, **kw)
                    else:
                        d, ids = self._stream_program(
                            self.store, self.frozen, queries, k, **kw)
                elif self.sharded_state is not None:
                    d, ids = self._sharded_program(
                        self.sharded_state, queries, k, mesh=self._mesh,
                        axis=self._shard_axis, **kw)
                else:
                    d, ids = self._program(self.state, queries, k, **kw)
            if t0 is not None:
                # blocks the result (an honest end-to-end number — the
                # caller's own block becomes a no-op), then records/samples
                with span("qpad.search.trace"):
                    tracer.on_search(self, queries, nq, k, kw, t0, d, ids)
            return d[:nq], ids[:nq]

    def _prepare(self, queries, k: int):
        """The host work before a search's program: check k, pad the batch
        to its bucket and normalize the knobs. Returns (padded queries,
        real rows, knobs)."""
        cfg = self.config
        ops = get_ops(cfg.index)
        # reject an unservable k eagerly (host-side, before any tracing)
        # instead of silently truncating the candidate list inside the scan
        _check_rerank_budget(cfg.target_dim is not None or ops.lossy,
                             cfg.rerank, k)
        queries = jnp.asarray(queries, jnp.float32)
        nq = queries.shape[0]
        bucket = _bucket(nq, cfg.query_bucket, cfg.small_batch)
        self.last_bucket = bucket
        if bucket != nq:
            queries = jnp.pad(queries, ((0, bucket - nq), (0, 0)))
        # normalize knobs the index kind can't observe so flipping them
        # (e.g. a stray nprobe on a flat engine) never re-keys the jit cache
        probed = cfg.index in ("ivf", "ivfpq")
        coded = cfg.index in ("pq", "opq", "ivfpq")
        kw = dict(nprobe=cfg.nprobe if probed else 0,
                  rerank=cfg.rerank,
                  backend=cfg.pq_backend if coded else "jnp",
                  lut_dtype=cfg.lut_dtype if coded else "f32",
                  scan_cap=0, prefilter=0)
        # small read-only ivfpq buckets: size the candidate gather by the
        # actual probed posting mass (compact scan) and — when the scan
        # space is the re-rank space — shrink the exact re-rank to the
        # certified survivors. Both are bit-identical to the defaults, so
        # engaging them per bucket only re-keys the cache, never results.
        # They engage independently: the compact scan wins whenever the
        # posting-mass bound clears _scan_cap's margin, but the pre-filter
        # pays only when the quantization/PQ error bound is tight enough to
        # actually cut survivors — on loose-bound corpora everyone survives,
        # the full-width fallback runs anyway, and the bound + partition
        # work is pure loss (~0.4-1.0ms per batch-64 call measured), so it
        # rides its own opt-in knob.
        if (cfg.index == "ivfpq" and self.store is None
                and self.sharded_state is None):
            if 0 < bucket <= cfg.compact_batch:
                kw["scan_cap"] = self._scan_cap(cfg.nprobe)
            if (0 < bucket <= cfg.prefilter_batch
                    and cfg.target_dim is None):
                r_s = max(2 * k, cfg.rerank // 2)
                if r_s < cfg.rerank:
                    kw["prefilter"] = r_s
        return queries, nq, kw


def build_engine(corpus: jax.Array, spec, **runtime) -> SearchEngine:
    """Build a serving engine from a pipeline spec — the canonical
    constructor of the composable API.

    ``spec`` is an ``IndexSpec``, a spec string
    (``"qpad32>ivf64x8>pq8x256:i8"``), or a full ``ServeConfig``;
    ``runtime`` forwards engine knobs the pipeline does not carry
    (``query_bucket``, ``mpad``, ``fit_sample``, ``seed``, ``stream``,
    ...). Continue with the lifecycle methods: ``.shard(mesh)``,
    ``.streaming(StreamConfig(...))``, ``.save(dir)``.
    """
    if isinstance(spec, ServeConfig):
        if runtime:
            spec = dataclasses.replace(spec, **runtime)
        return SearchEngine(corpus, spec)
    return SearchEngine(corpus, config_from_spec(spec, **runtime))
