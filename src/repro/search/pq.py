"""Product quantization (Jégou et al., TPAMI'11): the classic complement to
DR in vector-search memory hierarchies. MPAD reduces dimensionality; PQ
compresses the residual precision — together: f32 n-dim -> uint8 codes.

Asymmetric distance computation (ADC): per-query distance tables
(M x n_centroids) against subspace codebooks, then code lookups — no
decompression of the corpus. ``lut_dtype`` quantizes the tables themselves
(f32 -> bf16/int8, see ``repro.kernels.pq_adc.lut``) for a 2-4x LUT memory
cut on both scoring backends.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.pq_adc.lut import LUT_DTYPES, center_lut
from repro.kernels.pq_adc.ref import pq_adc_scores_ref
from .ivf import kmeans, sq_dists
from .knn import masked_topk

__all__ = ["PQIndex", "adc_tables", "build_pq", "lut_projection",
           "pq_local_scan", "pq_scan", "pq_search", "pq_reconstruct"]


class PQIndex(NamedTuple):
    codebooks: jax.Array    # (M, K, dsub)
    codes: jax.Array        # (N, M) uint8/int32 centroid ids
    lut_w: jax.Array        # (d, M*K) block-diagonal -2*codebook projection
    cbnorm: jax.Array       # (M, K) per-codeword squared norms


def lut_projection(codebooks: jax.Array):
    """Build-time table factorization: (lut_w (d, M*K), cbnorm (M, K)).

    The candidate-varying part of the per-query ADC tables is
    ``||cb||^2 - 2<q_m, cb[m,k]>``; ``lut_w`` stores the whole projection
    as one block-diagonal (d, M*K) matrix (block m = -2 * cb[m].T) — a
    single array any consumer can contract however its backend likes.
    ``adc_tables`` is the scan-path contraction of it.
    """
    m, kc, dsub = codebooks.shape
    w = jnp.zeros((m * dsub, m * kc), jnp.float32)
    for j in range(m):                                    # M small: unrolled
        w = w.at[j * dsub:(j + 1) * dsub, j * kc:(j + 1) * kc].set(
            -2.0 * codebooks[j].T)
    return w, jnp.sum(codebooks ** 2, -1)


def adc_tables(lut_w: jax.Array, cbnorm: jax.Array, q: jax.Array) -> jax.Array:
    """Per-query ADC tables (Q, M, K): ``cbnorm + (q @ lut_w).reshape``,
    contracted subspace-by-subspace.

    The dense (Q, d) @ (d, M*K) form spends M x the necessary FLOPs on the
    block-diagonal zeros; extracting the M diagonal (dsub, K) blocks (a
    32k-element gather) and running ONE batched ``dot_general`` over the
    subspace axis is ~3x faster at serving batches on CPU — and exact: the
    dropped products are exact zeros, so the result is bit-identical to
    the dense matmul. (The per-subspace einsum lowering XLA picks for
    ``qmd,mkd->qmk`` is far slower at batch >= 256; don't "simplify" back
    to it.)
    """
    m, kc = cbnorm.shape
    nq, d = q.shape
    dsub = d // m
    blocks = lut_w.reshape(m, dsub, m, kc)[
        jnp.arange(m), :, jnp.arange(m), :]               # (M, dsub, K)
    qs = q.reshape(nq, m, dsub).transpose(1, 0, 2)        # (M, Q, dsub)
    t = jax.lax.dot_general(qs, blocks, (((2,), (1,)), ((0,), (0,))))
    return cbnorm[None] + t.transpose(1, 0, 2)


def build_pq(key: jax.Array, x: jax.Array, m_subspaces: int = 8,
             n_centroids: int = 256, iters: int = 10) -> PQIndex:
    """Train per-subspace codebooks and encode the corpus."""
    x = jnp.asarray(x, jnp.float32)
    n, d = x.shape
    if d % m_subspaces:
        raise ValueError(f"dim {d} not divisible by M={m_subspaces}")
    dsub = d // m_subspaces
    xs = x.reshape(n, m_subspaces, dsub)
    cbs, codes = [], []
    for m in range(m_subspaces):
        sub = xs[:, m]
        cb = kmeans(jax.random.fold_in(key, m), sub,
                    min(n_centroids, n), iters)
        cbs.append(cb)
        codes.append(jnp.argmin(sq_dists(sub, cb), axis=1))
    cbs = jnp.stack(cbs)
    lut_w, cbnorm = lut_projection(cbs)
    # uint8 code storage end-to-end: both scoring backends gather the
    # narrow codes and widen in-register, so 4x fewer candidate bytes move
    code_dt = jnp.uint8 if n_centroids <= 256 else jnp.int32
    return PQIndex(codebooks=cbs,
                   codes=jnp.stack(codes, axis=1).astype(code_dt),
                   lut_w=lut_w, cbnorm=cbnorm)


def pq_reconstruct(index: PQIndex) -> jax.Array:
    """Decode the corpus (for error analysis): (N, D)."""
    m = index.codebooks.shape[0]
    parts = [index.codebooks[j][index.codes[:, j]] for j in range(m)]
    return jnp.concatenate(parts, axis=1)


def _check_adc_args(backend: str, lut_dtype: str):
    if backend not in ("jnp", "kernel"):
        raise ValueError(f"unknown ADC backend {backend!r}")
    if lut_dtype not in LUT_DTYPES:
        raise ValueError(
            f"unknown lut_dtype {lut_dtype!r}; expected one of {LUT_DTYPES}")


def pq_scan(index: PQIndex, q: jax.Array, k: int, backend: str = "jnp",
            lut_dtype: str = "f32"):
    """Unjitted ``pq_search`` core (inlineable into fused programs).

    Only the candidate-varying table part (||cb||^2 - 2<q, cb>) goes through
    the (possibly quantized) scan; the per-query constants — ||q||^2 and,
    when quantizing, the table row means (``center_lut``) — stay in f32 and
    are added back after top-k, so they cost no quantization range and
    cannot perturb the ranking.
    """
    _check_adc_args(backend, lut_dtype)
    q = jnp.asarray(q, jnp.float32)
    m, kc, dsub = index.codebooks.shape
    tables = adc_tables(index.lut_w, index.cbnorm, q)
    const = jnp.sum(q * q, axis=1)                        # (Q,) ||q||^2
    if lut_dtype != "f32":
        tables, offs = center_lut(tables)
        const = const + offs
    if backend == "kernel":
        from repro.kernels.pq_adc import pq_adc_topk_pallas
        d2, ids = pq_adc_topk_pallas(tables, index.codes, k,
                                     lut_dtype=lut_dtype)
    else:
        scores = pq_adc_scores_ref(tables, index.codes, lut_dtype)
        neg, ids = jax.lax.top_k(-scores, k)
        d2 = -neg
    return jnp.sqrt(jnp.maximum(d2 + const[:, None], 0.0)), ids


def pq_local_scan(lut_w: jax.Array, cbnorm: jax.Array, codes_loc: jax.Array,
                  q: jax.Array, n_cand: int, n_real: jax.Array, axis: str,
                  backend: str = "jnp", lut_dtype: str = "f32",
                  slack: int = 0,
                  live=None):
    """Shard-local plain-PQ ADC scan (a ``shard_map`` body of sharded
    serving): score this shard's row block of the code matrix and return
    **global** row ids via the shard offset.

    ``codes_loc`` is a (n_loc, M) block of the row-padded code matrix; rows
    whose global id (``axis_index * n_loc + row``) lands at or beyond
    ``n_real`` are shard padding and masked to (+inf, -1). On the kernel
    backend the fused scan cannot see the validity mask, so it over-fetches
    ``slack`` extra rows (>= the pad-row count, i.e. shards - 1) and drops
    pads post-hoc — see ``pq_adc_topk_global``. The per-query table is
    quantized exactly as on the single-device path; the centered constant
    is per-query and therefore ranking-invariant, so it is dropped here
    (final distances come from the exact re-rank).

    ``live`` (replicated (N,) bool, streaming serving) masks
    tombstoned/unallocated global rows before the local top-k. The fused
    kernel's validity handling is a prefix bound (``n_valid``), so an
    arbitrary tombstone bitmap needs ``backend="jnp"``.
    """
    _check_adc_args(backend, lut_dtype)
    q = jnp.asarray(q, jnp.float32)
    nq = q.shape[0]
    m, kc = cbnorm.shape
    tables = adc_tables(lut_w, cbnorm, q)
    if lut_dtype != "f32":
        tables, _ = center_lut(tables)
    n_loc = codes_loc.shape[0]
    off = jax.lax.axis_index(axis) * n_loc
    if backend == "kernel":
        if live is not None:
            raise ValueError(
                "pq_local_scan(live=...) needs backend='jnp': the "
                "shared-codes kernel only masks a row-count prefix")
        from repro.kernels.pq_adc.ops import pq_adc_topk_global
        return pq_adc_topk_global(tables, codes_loc, n_cand, row_offset=off,
                                  n_valid=n_real, slack=slack,
                                  lut_dtype=lut_dtype)
    scores = pq_adc_scores_ref(tables, codes_loc, lut_dtype)
    gid = off + jnp.arange(n_loc)
    ok = gid[None, :] < n_real
    if live is not None:
        n_cap = live.shape[0]
        ok = ok & live[jnp.clip(gid, 0, n_cap - 1)][None, :]
    scores = jnp.where(ok, scores, jnp.inf)
    return masked_topk(scores, jnp.broadcast_to(gid[None, :], scores.shape),
                       n_cand)


@functools.partial(jax.jit,
                   static_argnames=("k", "backend", "lut_dtype"))
def pq_search(index: PQIndex, q: jax.Array, k: int, backend: str = "jnp",
              lut_dtype: str = "f32"):
    """ADC top-k: returns (approx dists (Q,k), ids (Q,k)).

    ``backend="jnp"`` scores with vectorized table lookups; ``"kernel"``
    dispatches the fused Pallas ADC scan (``repro.kernels.pq_adc``),
    identical semantics, tiled + running top-k on device. ``lut_dtype``
    quantizes the per-query tables (both backends score through the same
    quantization, so they stay parity oracles of each other).
    """
    return pq_scan(index, q, k, backend, lut_dtype)
