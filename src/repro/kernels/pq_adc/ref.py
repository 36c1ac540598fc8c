"""Pure-jnp oracle for the fused PQ ADC scan: full distance-table lookups
plus ``lax.top_k``. Used for kernel parity tests and as the semantic spec.

Two variants mirror the two kernel entry points:

* shared codes — one (N, M) code matrix scanned by every query (plain PQ);
* gathered codes — per-query (C, M) candidate codes plus a per-candidate
  additive ``base`` term (the IVF-PQ residual decomposition: coarse distance
  + centroid/codeword cross term; see ``repro.search.ivfpq``).

The gathered variant has a second lowering with the same contract,
``pq_adc_gather_scores_onehot``, which the IVF-PQ scans use on a TPU; the
gather stays the oracle.

Every entry takes ``lut_dtype`` (see ``lut.py``): the oracle snaps the f32
tables onto exactly the kernel's bf16 / int8 grid but keeps the snapped
values in f32, so the scoring gather always runs the fast f32 path — on
CPU XLA a narrow-dtype gather is 2-3x SLOWER than the same gather in f32.
The snapped values are the narrow pipeline's values exactly: bf16 entries
are the bf16 roundings widened to f32, int8 entries are the integer codes
as f32 — per-candidate sums of <= M such integers are exact in f32, so
summing and applying the per-query ``scale`` once matches the kernel's
int32-accumulate path bit for bit. The quantization error itself is part
of the spec (bounded by ``lut_error_bound``).

The snap is wrapped in ``_pin`` (a ``lax.cond`` whose predicate is a
runtime value): without it XLA pulls the table-sized elementwise chain
INTO the kLoop fusion around the candidate gather and recomputes it per
*gathered* element — candidates outnumber table entries ~16x at serving
shapes, turning a ~0.2ms table pass into a ~2ms one. A conditional is a
separate XLA computation, so its result is materialized once
(``lax.optimization_barrier`` does NOT survive to the CPU fusion pass).

``scale`` (optional, int8 only) overrides the per-query quantization scale
with a caller-certified bound — it must be the same array the paired
kernel call gets, or the two backends land on different grids.

``center`` (optional, (Q, M) f32) subtracts a per-(query, subspace)
constant from the tables BEFORE the snap — the analytic row-mean centering
the IVF-PQ int8 scans use to halve the dynamic range the grid must cover.
The returned score then omits ``sum_m center[q, m]``; the caller adds it
back after top-k (a per-query constant never changes the ranking).

Codes may be uint8 (the stored width for K <= 256) or any int dtype; the
gather index is built at the narrowest width that spans ``M * K``, and the
gathers promise in-bounds indices (codes are < K by construction), which
drops take_along_axis's per-element wrap/oob-select chains.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .lut import _int8_scale, snap_values

__all__ = ["pq_adc_scores_ref", "pq_adc_topk_ref",
           "pq_adc_gather_scores_ref", "pq_adc_gather_scores_onehot",
           "pq_adc_gather_topk_ref"]


def _resolve_scale(tables, lut_dtype, scale, center):
    """Per-query int8 scale: caller-certified, or max|t - center| / 127."""
    if lut_dtype != "int8":
        return None
    if scale is not None:
        return jnp.asarray(scale, jnp.float32)
    ct = tables if center is None else tables - center[:, :, None]
    return _int8_scale(ct, None)


def _snap_tables(tables, lut_dtype, scale, center):
    """Center + grid-snap the (Q, M, K) tables, materialized (see module
    docs). The cond predicate is true for any finite table — i.e. for any
    finite query; a non-finite query takes the identity branch and scores
    with unsnapped tables, which is as meaningless as its input."""
    if lut_dtype == "f32":
        return tables

    def snap(tb):
        tc = tb if center is None else tb - center[:, :, None]
        return snap_values(tc, lut_dtype,
                           None if scale is None else scale[:, None, None])

    return jax.lax.cond(jnp.isfinite(tables[0, 0, 0]), snap,
                        lambda tb: tb, tables)


def pq_adc_scores_ref(tables: jax.Array, codes: jax.Array,
                      lut_dtype: str = "f32", scale=None,
                      center=None) -> jax.Array:
    """ADC distances, shared codes: out[q, n] = sum_m tables[q, m, codes[n, m]].

    tables (Q, M, K) f32; codes (N, M) uint8/int. Returns (Q, N) f32
    (minus ``sum_m center`` when ``center`` is given — see module docs).
    """
    tables = jnp.asarray(tables, jnp.float32)
    nq, m, _ = tables.shape
    scale = _resolve_scale(tables, lut_dtype, scale, center)
    ft = _snap_tables(tables, lut_dtype, scale, center)
    n = codes.shape[0]
    d2 = jnp.zeros((nq, n), jnp.float32)
    for j in range(m):                       # M small (4-16): unrolled
        d2 = d2 + jnp.take(ft[:, j, :], codes[:, j], axis=1, mode="clip")
    if lut_dtype == "int8":
        d2 = d2 * scale[:, None]             # exact integer sums, one rescale
    return d2


@functools.partial(jax.jit, static_argnames=("k", "lut_dtype"))
def pq_adc_topk_ref(tables: jax.Array, codes: jax.Array, k: int,
                    lut_dtype: str = "f32", scale=None, center=None):
    """Returns (d2 (Q, k) ascending, idx (Q, k)) over the shared code matrix."""
    d2 = pq_adc_scores_ref(tables, codes, lut_dtype, scale, center)
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx


def pq_adc_gather_scores_ref(tables: jax.Array, codes: jax.Array,
                             base: jax.Array, lut_dtype: str = "f32",
                             scale=None, center=None) -> jax.Array:
    """ADC distances, per-query candidate codes:

    out[q, c] = base[q, c] + sum_m tables[q, m, codes[q, c, m]].

    tables (Q, M, K) f32; codes (Q, C, M) uint8/int; base (Q, C) f32 (use
    +inf to mask padded candidates; ``base`` is never quantized). Returns
    (Q, C) f32 (minus ``sum_m center`` when ``center`` is given).

    The M per-subspace lookups are fused into ONE flattened gather over the
    (Q, M*K) grid-snapped f32 tables (flat index ``m*K + code``, int16 when
    the table fits) — identical semantics to the per-subspace loop, at the
    f32 gather speed regardless of ``lut_dtype``.
    """
    tables = jnp.asarray(tables, jnp.float32)
    nq, m, kc = tables.shape
    scale = _resolve_scale(tables, lut_dtype, scale, center)
    ft = _snap_tables(tables, lut_dtype, scale, center)
    c = codes.shape[1]
    idt = jnp.int16 if m * kc < 2 ** 15 else jnp.int32
    flat_idx = (codes.astype(idt)
                + jnp.arange(m, dtype=idt) * kc).reshape(nq, c * m)
    lut = jnp.take_along_axis(ft.reshape(nq, m * kc), flat_idx, axis=1,
                              mode="promise_in_bounds").reshape(nq, c, m)
    d2 = lut.sum(-1)
    if lut_dtype == "int8":
        d2 = d2 * scale[:, None]             # exact integer sums, one rescale
    return base.astype(jnp.float32) + d2


def pq_adc_gather_scores_onehot(tables: jax.Array, codes: jax.Array,
                                base: jax.Array, lut_dtype: str = "f32",
                                scale=None, center=None) -> jax.Array:
    """``pq_adc_gather_scores_ref``'s contract, scored with no gather:

    out[q, c] = base[q, c] + sum_{m, k} where(codes[q, c, m] == k,
                                              tables[q, m, k], 0).

    The TPU lowers the flattened ``take_along_axis`` to an element gather
    that reads ~4.7 KB of HBM per f32 it returns and holds ~36 B of
    temporaries per looked-up element (a v5e compile's cost and memory
    analysis); a compare-select-reduce over the codebook axis is VPU work
    that XLA fuses into one loop with no temporary. The codes are
    transposed to (Q, M, C), so the candidate axis is the lane axis of the
    broadcast (Q, M, K, C) operand and the reduce runs over (M, K), never
    over lanes.

    Exactly one k per (c, m) is selected, so every per-subspace term is the
    table entry exactly; only the order of the M-term sum may differ from
    the gather's. The grids are shared with the gather (``_snap_tables``,
    one int8 rescale after the exact integer sums), and +inf bases stay
    +inf. On XLA:CPU the gather is the fast form: this one does K times
    its arithmetic.
    """
    tables = jnp.asarray(tables, jnp.float32)
    kc = tables.shape[2]
    scale = _resolve_scale(tables, lut_dtype, scale, center)
    ft = _snap_tables(tables, lut_dtype, scale, center)
    # compare at the stored width (uint8 for K <= 256): a widened copy of
    # the transposed codes is a Q*C*M*4-byte temporary on the TPU
    ct = jnp.swapaxes(codes, 1, 2)                       # (Q, M, C)
    hit = ct[:, :, None, :] == jnp.arange(kc, dtype=ct.dtype)[:, None]
    d2 = jnp.sum(jnp.where(hit, ft[:, :, :, None], 0.0), axis=(1, 2))
    if lut_dtype == "int8":
        d2 = d2 * scale[:, None]             # exact integer sums, one rescale
    return base.astype(jnp.float32) + d2


@functools.partial(jax.jit, static_argnames=("k", "lut_dtype"))
def pq_adc_gather_topk_ref(tables: jax.Array, codes: jax.Array,
                           base: jax.Array, k: int, lut_dtype: str = "f32",
                           scale=None, center=None):
    """Returns (d2 (Q, k) ascending, idx (Q, k)); idx is the candidate slot."""
    d2 = pq_adc_gather_scores_ref(tables, codes, base, lut_dtype, scale,
                                  center)
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx
