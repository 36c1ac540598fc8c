"""Pallas TPU kernel: blocked L2 distances + running top-k.

The serving hot loop of the reduced-space scan (DESIGN.md §3.5). For a query
tile Q_blk (BQ x d) and database tile X_blk (BN x d):

  d2 = |q|^2 + |x|^2 - 2 q @ x^T      — the cross term is an MXU matmul
                                         (BQ x d) @ (d x BN)

and a running top-k buffer (BQ x K) is merged in-register. TPU Mosaic has no
general in-kernel sort/top_k, so the merge is K unrolled extract-min steps
built from vector min / compare / select + broadcasted_iota (first-occurrence
argmin trick) — O(K * BQ * BN) VPU work against O(BQ * BN * d) MXU work, i.e.
negligible for d >= K.

Grid (Q/BQ, N/BN), database axis fastest-varying; the top-k buffer block for
each query tile is revisited and updated across database tiles.

Layout notes: BQ, BN multiples of 128 keep the MXU fed and lanes full; the
distance tile (BQ x BN f32) plus both operand tiles bound VMEM:
128x512: 128*512*4 + (128+512)*d*4 ≈ 0.5 MiB at d=256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import resolve_interpret

_INF = float("inf")
_BIGI = 2**31 - 1


def _knn_kernel(n_total, k, q_ref, x_ref, best_d_ref, best_i_ref):
    j = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)                   # (BQ, d)
    xb = x_ref[...].astype(jnp.float32)                  # (BN, d)
    bq, bn = q.shape[0], xb.shape[0]
    qq = jnp.sum(q * q, axis=1, keepdims=True)
    xx = jnp.sum(xb * xb, axis=1)[None, :]
    cross = jax.lax.dot_general(
        q, xb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (BQ, BN) on the MXU
    d2 = jnp.maximum(qq + xx - 2.0 * cross, 0.0)
    gj = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    work = jnp.where(gj < n_total, d2, _INF)

    @pl.when(j == 0)
    def _init():
        best_d_ref[...] = jnp.full_like(best_d_ref, _INF)
        best_i_ref[...] = jnp.full_like(best_i_ref, -1)

    bd = best_d_ref[...]
    bi = best_i_ref[...]
    pos = jax.lax.broadcasted_iota(jnp.int32, bd.shape, 1)  # (BQ, K)
    for _ in range(k):                                   # unrolled extract-min
        m = jnp.min(work, axis=1)                        # (BQ,)
        col = jnp.min(jnp.where(work == m[:, None], gj, _BIGI), axis=1)
        worst = jnp.max(bd, axis=1)                      # (BQ,)
        wpos = jnp.min(jnp.where(bd == worst[:, None], pos, _BIGI), axis=1)
        better = (m < worst)[:, None]                    # (BQ, 1)
        sel = (pos == wpos[:, None]) & better
        bd = jnp.where(sel, m[:, None], bd)
        bi = jnp.where(sel, col[:, None], bi)
        work = jnp.where(gj == col[:, None], _INF, work)
    best_d_ref[...] = bd
    best_i_ref[...] = bi


@functools.partial(jax.jit,
                   static_argnames=("k", "block_q", "block_n", "interpret"))
def knn_topk_pallas(q: jax.Array, x: jax.Array, k: int,
                    block_q: int = 128, block_n: int = 512,
                    interpret: bool | None = None):
    """Blocked exact k-NN. Returns (d2 (Q,k) ascending, idx (Q,k))."""
    nq, d = q.shape
    n = x.shape[0]
    pad_q = (-nq) % block_q
    pad_n = (-n) % block_n
    qp = jnp.pad(q, ((0, pad_q), (0, 0))) if pad_q else q
    xp = jnp.pad(x, ((0, pad_n), (0, 0))) if pad_n else x
    grid = (qp.shape[0] // block_q, xp.shape[0] // block_n)
    bd, bi = pl.pallas_call(
        functools.partial(_knn_kernel, n, k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((qp.shape[0], k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(qp.astype(jnp.float32), xp.astype(jnp.float32))
    bd, bi = bd[:nq], bi[:nq]
    order = jnp.argsort(bd, axis=1)                      # ascending final sort
    return jnp.take_along_axis(bd, order, axis=1), jnp.take_along_axis(
        bi, order, axis=1)
