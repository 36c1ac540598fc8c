"""Jit'd public wrapper for the blocked k-NN Pallas kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import knn_topk_pallas


@functools.partial(jax.jit,
                   static_argnames=("k", "block_q", "block_n", "interpret"))
def knn_topk(q: jax.Array, x: jax.Array, k: int, *, block_q: int = 128,
             block_n: int = 512, interpret: bool | None = None):
    """Exact k-NN via the Pallas kernel: (dists (Q,k), idx (Q,k)), L2."""
    d2, idx = knn_topk_pallas(q, x, k, block_q=block_q, block_n=block_n,
                              interpret=interpret)
    return jnp.sqrt(jnp.maximum(d2, 0.0)), idx
