"""The plain exact k-NN that recall and returned distances are judged by.

``exact_knn`` is ``repro.search.knn.knn_search_blocked`` (a running top-k
over row blocks, inner products at ``Precision.HIGHEST``) copied here and
given a live row range ``[lo, hi)`` per query: the rows a stream cell holds
live at the moment a search was due are a contiguous range of its row
array, since ids are assigned in order and the oldest are deleted first.

``precision="bf16"`` computes the same search from bfloat16 inputs with f32
accumulation, a TPU's default matmul: the control that a comparison has to
fail. ``true_dists`` recomputes the distance of each returned id in
float64 on the host, so the re-rank's returned distances are checked
directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["exact_knn", "true_dists", "recall_rows"]

QUERY_BLOCK = 512


@functools.partial(jax.jit, static_argnames=("k", "block", "precision"))
def _knn_block(q, x, lo, hi, k, block, precision):
    nq, dim = q.shape
    n_blocks = x.shape[0] // block
    xb = x.reshape(n_blocks, block, dim)
    if precision == "bf16":
        q, xb = q.astype(jnp.bfloat16), xb.astype(jnp.bfloat16)
        prec = jax.lax.Precision.DEFAULT
    else:
        prec = jax.lax.Precision.HIGHEST
    qq = jnp.sum(jnp.square(q.astype(jnp.float32)), axis=-1)[:, None]

    def scan_block(carry, blk):
        best_d, best_i, offset = carry
        xx = jnp.sum(jnp.square(blk.astype(jnp.float32)), axis=-1)[None, :]
        dot = jnp.matmul(q, blk.T, precision=prec,
                         preferred_element_type=jnp.float32)
        d2 = jnp.maximum(qq + xx - 2.0 * dot, 0.0)
        idx = offset + jnp.arange(block, dtype=jnp.int32)[None, :]
        live = (idx >= lo[:, None]) & (idx < hi[:, None])
        d2 = jnp.where(live, d2, jnp.inf)
        cand_d = jnp.concatenate([best_d, d2], axis=1)
        cand_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(idx, d2.shape)], axis=1)
        neg, sel = jax.lax.top_k(-cand_d, k)
        return (-neg, jnp.take_along_axis(cand_i, sel, axis=1),
                offset + block), None

    init = (jnp.full((nq, k), jnp.inf), jnp.full((nq, k), -1, jnp.int32),
            jnp.zeros((), jnp.int32))
    (best_d, best_i, _), _ = jax.lax.scan(scan_block, init, xb)
    return jnp.sqrt(best_d), best_i


def exact_knn(queries, rows, k: int, lo=None, hi=None, *, block: int = 8192,
              precision: str = "highest"):
    """Exact k nearest rows (L2) of each query among rows ``[lo, hi)``.

    ``queries`` (Q, D) and ``rows`` (N, D) are device or host arrays;
    ``lo``/``hi`` are per-query int arrays (default: every row). Runs in
    blocks of ``QUERY_BLOCK`` queries and ``block`` rows so that it fits
    beside nothing else. Returns host arrays (dists (Q, k), ids (Q, k)).
    """
    if precision not in ("highest", "bf16"):
        raise ValueError(f"precision {precision!r}")
    rows = jnp.asarray(rows, jnp.float32)
    n = rows.shape[0]
    block = min(block, max(8, n))
    pad = (-n) % block
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.zeros((pad, rows.shape[1]), rows.dtype)], axis=0)
    queries = np.asarray(queries, np.float32)
    nq = queries.shape[0]
    lo = np.zeros(nq, np.int32) if lo is None else np.asarray(lo, np.int32)
    hi = np.full(nq, n, np.int32) if hi is None else np.asarray(hi, np.int32)
    qb = min(QUERY_BLOCK, nq)
    out_d, out_i = [], []
    for s in range(0, nq, qb):
        e = min(s + qb, nq)
        padq = qb - (e - s)
        q = np.pad(queries[s:e], ((0, padq), (0, 0)))
        blo = np.pad(lo[s:e], (0, padq))
        bhi = np.pad(hi[s:e], (0, padq))
        d, i = _knn_block(jnp.asarray(q), rows, jnp.asarray(blo),
                          jnp.asarray(bhi), k, block, precision)
        out_d.append(np.asarray(d)[:e - s])
        out_i.append(np.asarray(i)[:e - s])
    return np.concatenate(out_d), np.concatenate(out_i)


def true_dists(queries, rows_by_id) -> np.ndarray:
    """float64 L2 distance of each query to each of its returned rows:
    ``queries`` (Q, D), ``rows_by_id`` (Q, k, D), both host arrays."""
    q = np.asarray(queries, np.float64)[:, None, :]
    x = np.asarray(rows_by_id, np.float64)
    return np.sqrt(np.sum(np.square(x - q), axis=-1))


def recall_rows(found: np.ndarray, truth: np.ndarray) -> np.ndarray:
    """Per-query recall@k: |found ∩ truth| / k, shapes (Q, k). Copied from
    ``repro.search.knn.recall_at_k``, which averages the same rows; -1 ids
    in ``truth`` (fewer than k live rows) are not counted as hits."""
    hit = (found[:, :, None] == truth[:, None, :]) & (truth[:, None, :] >= 0)
    return hit.any(axis=2).sum(axis=1) / truth.shape[1]
