"""Brute-force k-NN and the paper's A_m(k) neighbor-preservation metric.

``knn_search`` is the single-shot exact search (full distance matrix);
``knn_search_blocked`` streams the database in blocks with a running top-k so
memory stays O(Q·(k+block)) — the jnp mirror of the Pallas kernel in
``repro.kernels.knn_topk``.

Both are the exact oracle that recall is scored against, so their inner
products run at ``Precision.HIGHEST``: a TPU otherwise runs an f32 matmul
as one bf16 pass, and a recall against a bf16 oracle is not a recall.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["knn_scan", "knn_search", "knn_search_blocked", "masked_topk",
           "recall_at_k", "amk_accuracy"]


_EXACT = jax.lax.Precision.HIGHEST


def _sq_dists(q: jax.Array, x: jax.Array) -> jax.Array:
    qq = jnp.sum(q * q, axis=-1)[:, None]
    xx = jnp.sum(x * x, axis=-1)[None, :]
    return jnp.maximum(qq + xx - 2.0 * jnp.matmul(q, x.T, precision=_EXACT),
                       0.0)


def knn_scan(q: jax.Array, x: jax.Array, k: int):
    """Unjitted ``knn_search`` core (inlineable into fused programs).

    Tolerates k > N (a candidate budget above the corpus size): the short
    rows are right-padded with (-1, inf), matching the IVF pad convention.
    """
    d2 = _sq_dists(q, x)
    k_eff = min(k, x.shape[0])
    neg, idx = jax.lax.top_k(-d2, k_eff)
    if k_eff < k:
        neg = jnp.pad(neg, ((0, 0), (0, k - k_eff)),
                      constant_values=-jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, k - k_eff)), constant_values=-1)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx


def masked_topk(d2: jax.Array, ids: jax.Array, k: int):
    """Row-wise top-k of masked distances carrying payload ids.

    ``d2`` (Q, C) with +inf marking invalid entries; ``ids`` (Q, C) the
    payload (e.g. global row ids) returned for the surviving slots. Invalid
    or missing slots come back as (+inf, -1); tolerates k > C by
    right-padding — the shared pad convention of every scan in this package.
    The building block of the per-shard local scans in sharded serving.
    """
    k_eff = min(k, d2.shape[1])
    neg, sel = jax.lax.top_k(-d2, k_eff)
    out_i = jnp.where(jnp.isneginf(neg), -1,
                      jnp.take_along_axis(ids, sel, axis=1))
    out_d = -neg
    if k_eff < k:
        out_d = jnp.pad(out_d, ((0, 0), (0, k - k_eff)),
                        constant_values=jnp.inf)
        out_i = jnp.pad(out_i, ((0, 0), (0, k - k_eff)),
                        constant_values=-1)
    return out_d, out_i


@functools.partial(jax.jit, static_argnames=("k",))
def knn_search(q: jax.Array, x: jax.Array, k: int):
    """Exact k-NN: returns (dists (Q,k), indices (Q,k)) by L2 distance."""
    return knn_scan(q, x, k)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def knn_search_blocked(q: jax.Array, x: jax.Array, k: int, block: int = 1024):
    """Streaming exact k-NN with a running top-k over database blocks."""
    nq = q.shape[0]
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x = jnp.concatenate(
            [x, jnp.full((pad, x.shape[1]), jnp.inf, x.dtype)], axis=0)
    n_blocks = x.shape[0] // block
    xb = x.reshape(n_blocks, block, x.shape[1])
    qq = jnp.sum(q * q, axis=-1)[:, None]

    def scan_block(carry, xblk):
        best_d, best_i, offset = carry
        xx = jnp.sum(xblk * xblk, axis=-1)[None, :]
        d2 = qq + xx - 2.0 * jnp.matmul(q, xblk.T, precision=_EXACT)
        d2 = jnp.where(jnp.isfinite(xx), jnp.maximum(d2, 0.0), jnp.inf)
        idx = offset + jnp.arange(block)[None, :]
        cand_d = jnp.concatenate([best_d, d2], axis=1)
        cand_i = jnp.concatenate([best_i, jnp.broadcast_to(idx, d2.shape)], axis=1)
        neg, sel = jax.lax.top_k(-cand_d, k)
        return (-neg, jnp.take_along_axis(cand_i, sel, axis=1), offset + block), None

    init = (jnp.full((nq, k), jnp.inf), jnp.zeros((nq, k), jnp.int32),
            jnp.zeros((), jnp.int32))
    (best_d, best_i, _), _ = jax.lax.scan(scan_block, init, xb)
    return jnp.sqrt(jnp.maximum(best_d, 0.0)), best_i


def recall_at_k(found: jax.Array, truth: jax.Array) -> jax.Array:
    """|found ∩ truth| / k per query, averaged. Shapes (Q, k) int."""
    inter = (found[:, :, None] == truth[:, None, :]).any(axis=2)
    return jnp.mean(jnp.sum(inter, axis=1) / truth.shape[1])


def amk_accuracy(reducer, x_train: jax.Array, y_test: jax.Array, k: int,
                 block: int | None = None) -> jax.Array:
    """The paper's A_m(k) (Section 3.2).

    For each test vector y_i: k-NN in the *original* space X vs k-NN of f(y_i)
    in the *reduced* set f(X); A_m(k) = mean fraction retained.
    """
    if block is None:
        _, truth = knn_search(y_test, x_train, k)
    else:
        _, truth = knn_search_blocked(y_test, x_train, k, block=block)
    xr = reducer(x_train) if callable(reducer) else reducer.transform(x_train)
    yr = reducer(y_test) if callable(reducer) else reducer.transform(y_test)
    xr = jnp.asarray(xr, jnp.float32)
    yr = jnp.asarray(yr, jnp.float32)
    if block is None:
        _, found = knn_search(yr, xr, k)
    else:
        _, found = knn_search_blocked(yr, xr, k, block=block)
    return recall_at_k(found, truth)
