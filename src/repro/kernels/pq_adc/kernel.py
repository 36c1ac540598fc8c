"""Pallas TPU kernel: tiled fused PQ ADC scan + running top-k.

The serving hot loop of a PQ / IVF-PQ index: per-query distance tables
T (Q x M x K) against the corpus code matrix C (N x M),

  d2[q, n] = sum_m T[q, m, C[n, m]]

The per-subspace table lookup is lane-hostile as a gather, so each subspace
is materialised as a one-hot matmul on the MXU: for a code tile (BN,) build
onehot (K x BN) with broadcasted_iota and contract T[:, m, :] (BQ x K)
against it — K is the codebook size (<=256), so the one-hot tile is small
and the MXU does BQ x K x BN useful work per subspace. Distances accumulate
in VMEM across the M unrolled subspaces; a running top-k buffer (BQ x K_top)
is merged across database tiles with the same K unrolled extract-min steps
as ``knn_topk`` (no in-kernel sort on Mosaic).

Grid (Q/BQ, N/BN), database axis fastest-varying; the top-k block for each
query tile is revisited and updated across database tiles.

Quantized LUTs (``lut_dtype``, see ``lut.py``): tables enter the kernel in
f32, bf16, or int8. bf16 contracts on the bf16 MXU path with f32
accumulation; int8 contracts int8 x int8 -> int32 and one per-query f32
``scale`` multiply (an extra (BQ, 1) input block) restores the distance
unit after the M subspaces accumulate — the integer partial sums are exact,
so the only error is the table rounding itself. VMEM for the tables drops
2x / 4x accordingly.

Two entry points share the merge:

* ``pq_adc_topk_pallas``       — shared (N, M) codes, plain-PQ scan;
* ``pq_adc_gather_topk_pallas``— per-query (C, M) candidate codes plus a
  per-candidate additive ``base`` (the IVF-PQ residual decomposition). The
  lookup here is per-query, so the one-hot contraction runs on the VPU
  ((BQ, BN, K) masked sum — int32 select/add for int8). Tables and codes
  enter subspace-leading, (M, Q, K) and (M, Q, C), and a ``fori_loop``
  walks M on that untiled axis, so only one subspace's (BQ, BN, K)
  temporaries are live: the default blocks fit the 16 MiB scoped VMEM of
  a v5e at every width up to M=16, K=256 (an unrolled loop did not).

Both entry points take ``interpret=None``: the platform decides
(``repro.kernels.platform.resolve_interpret`` — compiled on a TPU,
interpreted elsewhere).

Layout notes: codes enter the shared kernel transposed (M, N) so a subspace
row slice is a native (1, BN) lane vector; VMEM at defaults
(BQ=128, BN=512, M=16, K=256): tables 2 MiB f32 / 1 MiB bf16 / 0.5 MiB int8
+ onehot 0.5 MiB + d2 0.25 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import resolve_interpret
from .lut import quantize_lut

_INF = float("inf")
_BIGI = 2**31 - 1


def _merge_topk(work, gj, bd, bi, k):
    """Merge a masked (BQ, BN) distance tile into running top-k buffers.

    K unrolled extract-min steps from vector min/compare/select +
    broadcasted_iota (first-occurrence argmin trick) — O(k·BQ·BN) VPU work.
    """
    pos = jax.lax.broadcasted_iota(jnp.int32, bd.shape, 1)   # (BQ, K_top)
    for _ in range(k):
        m = jnp.min(work, axis=1)                            # (BQ,)
        col = jnp.min(jnp.where(work == m[:, None], gj, _BIGI), axis=1)
        worst = jnp.max(bd, axis=1)                          # (BQ,)
        wpos = jnp.min(jnp.where(bd == worst[:, None], pos, _BIGI), axis=1)
        better = (m < worst)[:, None]                        # (BQ, 1)
        sel = (pos == wpos[:, None]) & better
        bd = jnp.where(sel, m[:, None], bd)
        bi = jnp.where(sel, col[:, None], bi)
        work = jnp.where(gj == col[:, None], _INF, work)
    return bd, bi


def _adc_kernel(n_total, k, lut_dtype, t_ref, *refs):
    if lut_dtype == "int8":
        s_ref, c_ref, best_d_ref, best_i_ref = refs
    else:
        (c_ref, best_d_ref, best_i_ref), s_ref = refs, None
    j = pl.program_id(1)
    tables = t_ref[...]                                      # (BQ, M, K)
    bq, m, kc = tables.shape
    bn = c_ref.shape[1]
    cent = jax.lax.broadcasted_iota(jnp.int32, (kc, bn), 0)
    if lut_dtype == "int8":
        acc = jnp.zeros((bq, bn), jnp.int32)
        for sub in range(m):                                 # M static: unroll
            # codes arrive at stored width (uint8); widen the (1, BN) slice
            # in-register — HBM traffic stays 1 byte/code
            row = c_ref[sub:sub + 1, :].astype(jnp.int32)
            onehot = (row == cent).astype(jnp.int8)
            acc = acc + jax.lax.dot_general(
                tables[:, sub, :], onehot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)            # int8 MXU path
        d2 = acc.astype(jnp.float32) * s_ref[...]            # (BQ,BN)*(BQ,1)
    else:
        d2 = jnp.zeros((bq, bn), jnp.float32)
        for sub in range(m):                                 # M static: unroll
            row = c_ref[sub:sub + 1, :].astype(jnp.int32)
            onehot = (row == cent).astype(tables.dtype)
            d2 = d2 + jax.lax.dot_general(
                tables[:, sub, :], onehot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # MXU (BQ,K)@(K,BN)
    gj = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    work = jnp.where(gj < n_total, d2, _INF)

    @pl.when(j == 0)
    def _init():
        best_d_ref[...] = jnp.full_like(best_d_ref, _INF)
        best_i_ref[...] = jnp.full_like(best_i_ref, -1)

    bd, bi = _merge_topk(work, gj, best_d_ref[...], best_i_ref[...], k)
    best_d_ref[...] = bd
    best_i_ref[...] = bi


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "interpret", "lut_dtype"))
def pq_adc_topk_pallas(tables: jax.Array, codes: jax.Array, k: int,
                       block_q: int = 128, block_n: int = 512,
                       interpret: bool | None = None, lut_dtype: str = "f32",
                       scale=None):
    """Fused ADC scan over a shared code matrix.

    tables (Q, M, K) f32 (quantized internally per ``lut_dtype``; ``scale``
    optionally overrides the per-query int8 scale with a caller-certified
    bound — see ``lut.quantize_lut``);
    codes (N, M) int — kept at stored width (uint8 for K <= 256) through
    the HBM->VMEM pipeline and widened in-register per subspace. Returns
    (d2 (Q, k) ascending, idx (Q, k) int32 ids into the code matrix).
    """
    nq, m, kc = tables.shape
    n = codes.shape[0]
    qt, scale = quantize_lut(tables, lut_dtype, scale)
    pad_q = (-nq) % block_q
    pad_n = (-n) % block_n
    tp = jnp.pad(qt, ((0, pad_q), (0, 0), (0, 0))) if pad_q else qt
    cp = jnp.pad(codes, ((0, pad_n), (0, 0))) if pad_n else codes
    grid = (tp.shape[0] // block_q, cp.shape[0] // block_n)
    inputs = [tp, cp.T]                       # codes at stored width (uint8)
    in_specs = [
        pl.BlockSpec((block_q, m, kc), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((m, block_n), lambda i, j: (0, j)),
    ]
    if lut_dtype == "int8":
        sp = jnp.pad(scale, (0, pad_q)) if pad_q else scale
        inputs.insert(1, sp[:, None].astype(jnp.float32))
        in_specs.insert(1, pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)))
    bd, bi = pl.pallas_call(
        functools.partial(_adc_kernel, n, k, lut_dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((tp.shape[0], k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(*inputs)
    bd, bi = bd[:nq], bi[:nq]
    order = jnp.argsort(bd, axis=1)                          # ascending sort
    return (jnp.take_along_axis(bd, order, axis=1),
            jnp.take_along_axis(bi, order, axis=1))


def _adc_gather_kernel(c_total, k, lut_dtype, t_ref, *refs):
    if lut_dtype == "int8":
        s_ref, c_ref, base_ref, best_d_ref, best_i_ref = refs
    else:
        (c_ref, base_ref, best_d_ref, best_i_ref), s_ref = refs, None
    j = pl.program_id(1)
    m, bq, kc = t_ref.shape                                  # (M, BQ, K)
    bn = c_ref.shape[2]                                      # (M, BQ, BN)
    acc_dt = jnp.int32 if lut_dtype == "int8" else jnp.float32

    def subspace(sub, acc):
        # one subspace per loop step: the (BQ, BN, K) iota and select live
        # for one step only, so VMEM does not grow with M. ``sub`` indexes
        # the untiled leading axis, which Mosaic slices at any offset.
        codes = c_ref[sub].astype(jnp.int32)                 # (BQ, BN)
        t = t_ref[sub].astype(acc_dt)                        # (BQ, K)
        cent = jax.lax.broadcasted_iota(jnp.int32, (bq, bn, kc), 2)
        hit = codes[:, :, None] == cent
        return acc + jnp.sum(jnp.where(hit, t[:, None, :], 0), axis=2)

    acc = jax.lax.fori_loop(0, m, subspace, jnp.zeros((bq, bn), acc_dt))
    lut = acc.astype(jnp.float32)
    if lut_dtype == "int8":
        lut = lut * s_ref[...]                               # (BQ,BN)*(BQ,1)
    d2 = base_ref[...].astype(jnp.float32) + lut
    gj = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    work = jnp.where(gj < c_total, d2, _INF)

    @pl.when(j == 0)
    def _init():
        best_d_ref[...] = jnp.full_like(best_d_ref, _INF)
        best_i_ref[...] = jnp.full_like(best_i_ref, -1)

    bd, bi = _merge_topk(work, gj, best_d_ref[...], best_i_ref[...], k)
    best_d_ref[...] = bd
    best_i_ref[...] = bi


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "interpret", "lut_dtype"))
def pq_adc_gather_topk_pallas(tables: jax.Array, codes: jax.Array,
                              base: jax.Array, k: int,
                              block_q: int = 8, block_n: int = 256,
                              interpret: bool | None = None,
                              lut_dtype: str = "f32", scale=None):
    """Fused ADC scan over per-query gathered candidate codes.

    tables (Q, M, K) f32 (quantized internally per ``lut_dtype``; ``scale``
    optionally overrides the per-query int8 scale with a caller-certified
    bound — see ``lut.quantize_lut``);
    codes (Q, C, M) int — kept at stored width (uint8 for K <= 256), so
    candidate-code HBM traffic is 1 byte/code; base (Q, C) f32 additive
    term (+inf masks padded candidates; never quantized). Returns
    (d2 (Q, k) ascending, idx (Q, k) int32 candidate-slot ids in [0, C)).
    """
    nq, m, kc = tables.shape
    c = codes.shape[1]
    qt, scale = quantize_lut(tables, lut_dtype, scale)
    pad_q = (-nq) % block_q
    pad_c = (-c) % block_n
    tp = jnp.pad(qt, ((0, pad_q), (0, 0), (0, 0))) if pad_q else qt
    cp = jnp.pad(codes, ((0, pad_q), (0, pad_c), (0, 0)))
    bp = jnp.pad(base, ((0, pad_q), (0, pad_c)), constant_values=_INF)
    grid = (tp.shape[0] // block_q, cp.shape[1] // block_n)
    # subspace-leading layouts: the kernel loops over M on the untiled
    # axis, and the candidate axis of the codes is lane-dense
    inputs = [tp.transpose(1, 0, 2), cp.transpose(2, 0, 1),
              bp.astype(jnp.float32)]           # codes at stored width (uint8)
    in_specs = [
        pl.BlockSpec((m, block_q, kc), lambda i, j: (0, i, 0)),
        pl.BlockSpec((m, block_q, block_n), lambda i, j: (0, i, j)),
        pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
    ]
    if lut_dtype == "int8":
        sp = jnp.pad(scale, (0, pad_q)) if pad_q else scale
        inputs.insert(1, sp[:, None].astype(jnp.float32))
        in_specs.insert(1, pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)))
    bd, bi = pl.pallas_call(
        functools.partial(_adc_gather_kernel, c, k, lut_dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((tp.shape[0], k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(*inputs)
    bd, bi = bd[:nq], bi[:nq]
    order = jnp.argsort(bd, axis=1)
    return (jnp.take_along_axis(bd, order, axis=1),
            jnp.take_along_axis(bi, order, axis=1))
