"""Pallas TPU kernel for MPAD pairwise-threshold statistics.

The paper's O(N^2) hot loop (Algorithm 1 steps 5-8) recast for the TPU memory
hierarchy (DESIGN.md §3): instead of materializing + sorting N^2/2 pairwise
distances in HBM, the kernel streams (BI x BJ) tiles of the implicit
difference matrix through VMEM and reduces them to O(N) outputs:

  out c     (N,1) f32 — signed within-threshold counts (gradient coefficients)
  out cnt   (1,1) i32 — #ordered pairs within tau (halve for unordered)
  out sum   (1,1) f32 — sum of |p_i-p_j| over ordered pairs within tau (halve)

Grid is (N/BI, N/BJ); the j axis is the fastest-varying (sequential) axis so
the c-block for row-tile i is revisited and accumulated across j — the
standard Pallas accumulate-over-grid pattern. Block sizes default to 256
(lane-aligned multiples of 128).

VMEM working set per step: BI + BJ scalars + one BI x BJ f32 tile
(256x256x4 = 256 KiB), far under the ~16 MiB VMEM budget; larger BJ (512/1024)
raises arithmetic intensity if needed — the kernel is compute-bound on the
VPU (no MXU work), which is what frees the MXU-bound matmuls elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import resolve_interpret

DEFAULT_BLOCK = 256


def _stats_kernel(n_total, pi_ref, pj_ref, tau_ref, c_ref, cnt_ref, s_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    bi = pi_ref.shape[0]
    bj = pj_ref.shape[0]
    pi = pi_ref[:, 0]
    pj = pj_ref[:, 0]
    diff = pi[:, None] - pj[None, :]                       # (BI, BJ)
    ad = jnp.abs(diff)
    gi = i * bi + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 0)
    gj = j * bj + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1)
    valid = (gi != gj) & (gi < n_total) & (gj < n_total)
    mask = (ad <= tau_ref[0, 0]) & valid

    @pl.when(j == 0)
    def _init_c():
        c_ref[...] = jnp.zeros_like(c_ref)

    c_ref[:, 0] += jnp.sum(jnp.where(mask, jnp.sign(diff), 0.0), axis=1)

    @pl.when((i == 0) & (j == 0))
    def _init_scalars():
        cnt_ref[0, 0] = 0
        s_ref[0, 0] = 0.0

    cnt_ref[0, 0] += jnp.sum(mask.astype(jnp.int32))
    s_ref[0, 0] += jnp.sum(jnp.where(mask, ad, 0.0))


@functools.partial(jax.jit,
                   static_argnames=("block_i", "block_j", "interpret"))
def pairwise_stats_pallas(p: jax.Array, tau: jax.Array,
                          block_i: int = DEFAULT_BLOCK,
                          block_j: int = DEFAULT_BLOCK,
                          interpret: bool | None = None):
    """Tiled threshold statistics. Returns (count i32, sum f32, coeff (N,))."""
    n = p.shape[0]
    pad = (-n) % max(block_i, block_j)
    p_padded = jnp.pad(p, (0, pad)) if pad else p
    np_ = p_padded.shape[0]
    p2 = p_padded.reshape(np_, 1).astype(jnp.float32)
    tau2 = jnp.reshape(tau, (1, 1)).astype(jnp.float32)
    grid = (np_ // block_i, np_ // block_j)
    c, cnt, s = pl.pallas_call(
        functools.partial(_stats_kernel, n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_j, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(p2, p2, tau2)
    coeff = c[:n, 0]
    return cnt[0, 0] // 2, s[0, 0] * 0.5, coeff
