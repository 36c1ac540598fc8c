"""Jit'd public wrappers for the fused ADC-scan Pallas kernels."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import pq_adc_gather_topk_pallas, pq_adc_topk_pallas


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "interpret", "lut_dtype"))
def pq_adc_topk(tables: jax.Array, codes: jax.Array, k: int, *,
                block_q: int = 128, block_n: int = 512,
                interpret: bool | None = None, lut_dtype: str = "f32"):
    """Top-k ADC over shared codes: (dists (Q,k), idx (Q,k)), sqrt'd."""
    with jax.named_scope("pq_adc.topk"):
        d2, idx = pq_adc_topk_pallas(tables, codes, k, block_q=block_q,
                                     block_n=block_n, interpret=interpret,
                                     lut_dtype=lut_dtype)
        return jnp.sqrt(jnp.maximum(d2, 0.0)), idx


@functools.partial(jax.jit, static_argnames=("k", "slack", "block_q",
                                             "block_n", "interpret",
                                             "lut_dtype"))
def pq_adc_topk_global(tables: jax.Array, codes: jax.Array, k: int, *,
                       row_offset: jax.Array, n_valid: jax.Array,
                       slack: int = 0, block_q: int = 128,
                       block_n: int = 512, interpret: bool | None = None,
                       lut_dtype: str = "f32"):
    """Shard-local fused ADC scan returning GLOBAL row ids (sharded serving).

    Runs the shared-codes kernel over one shard's (n_loc, M) row block and
    maps the local hits to global ids via ``row_offset`` (this shard's
    first global row). The kernel cannot see the shard-pad validity mask,
    so the scan over-fetches ``k + slack`` rows (``slack`` >= the possible
    pad-row count, i.e. shards - 1), drops hits with global id >=
    ``n_valid`` post-hoc, and re-top-ks to k — pad rows can then never
    displace a real candidate. Returns (d2 (Q, k), global ids (Q, k)) with
    (+inf, -1) on unfilled slots; d2 is NOT sqrt'd (merge key only).
    """
    n_loc = codes.shape[0]
    kk = min(k + slack, n_loc)
    with jax.named_scope("pq_adc.topk_global"):
        d2, idx = pq_adc_topk_pallas(tables, codes, kk, block_q=block_q,
                                     block_n=block_n, interpret=interpret,
                                     lut_dtype=lut_dtype)
        gid = row_offset + idx
        bad = (idx < 0) | (gid >= n_valid)
        # (+inf, -1) pad convention + masked re-top-k mirror
        # repro.search.knn.masked_topk (importing it here would cycle
        # kernels -> search -> kernels); keep the two in step
        d2 = jnp.where(bad, jnp.inf, d2)
        gid = jnp.where(bad, -1, gid)
        if kk > k:
            neg, sel = jax.lax.top_k(-d2, k)
            d2 = -neg
            gid = jnp.take_along_axis(gid, sel, axis=1)
        elif kk < k:
            d2 = jnp.pad(d2, ((0, 0), (0, k - kk)),
                         constant_values=jnp.inf)
            gid = jnp.pad(gid, ((0, 0), (0, k - kk)), constant_values=-1)
        return d2, gid


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "interpret", "lut_dtype"))
def pq_adc_gather_topk(tables: jax.Array, codes: jax.Array, base: jax.Array,
                       k: int, *, block_q: int = 8, block_n: int = 256,
                       interpret: bool | None = None, lut_dtype: str = "f32"):
    """Top-k ADC over per-query candidates: (dists (Q,k), slot idx (Q,k))."""
    with jax.named_scope("pq_adc.gather_topk"):
        d2, idx = pq_adc_gather_topk_pallas(tables, codes, base, k,
                                            block_q=block_q,
                                            block_n=block_n,
                                            interpret=interpret,
                                            lut_dtype=lut_dtype)
        return jnp.sqrt(jnp.maximum(d2, 0.0)), idx
