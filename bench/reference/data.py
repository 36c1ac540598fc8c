"""Seeded data for every cell: corpus rows, the query pool and the rows
that a stream cell upserts, all drawn from one Gaussian mixture on the
device.

``make_clustered`` is a copy of ``repro.data.synthetic.make_clustered``
(the program's generic mixture generator), kept here so that no change to
the program can move the benchmark's data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["seed_key", "make_clustered", "make_data"]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits. ``jax.random.key`` keeps
    only the low 32 bits of its argument without 64-bit mode, so the high
    word is folded in: seeds that differ only above bit 31 stay apart."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


# copied from repro.data.synthetic.make_clustered
def make_clustered(key, n_train, n_test, dim, n_clusters=16, spread=0.35,
                   center_scale=1.0):
    """Gaussian mixture: (train (n_train, dim), test (n_test, dim)) f32."""
    kc, kl, kn, kl2, kn2 = jax.random.split(key, 5)
    centers = jax.random.normal(kc, (n_clusters, dim)) * center_scale
    lab = jax.random.randint(kl, (n_train,), 0, n_clusters)
    xtr = centers[lab] + spread * jax.random.normal(kn, (n_train, dim))
    lab2 = jax.random.randint(kl2, (n_test,), 0, n_clusters)
    xte = centers[lab2] + spread * jax.random.normal(kn2, (n_test, dim))
    return xtr.astype(jnp.float32), xte.astype(jnp.float32)


def make_data(seed: int, rows: int, extra_rows: int, queries: int, dim: int,
              rows_per_component: int):
    """(corpus (rows, dim), inserts (extra_rows, dim), pool (queries, dim)).

    The corpus and the rows a stream cell inserts are one draw from the
    mixture, split, so inserts land in the corpus's own clusters; the
    pool is the mixture's held-out draw. One jitted call on the device.
    """
    n_clusters = max(16, rows // rows_per_component)
    return _draw(seed_key(seed), rows, extra_rows, queries, dim, n_clusters)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _draw(key, rows, extra_rows, queries, dim, n_clusters):
    train, pool = make_clustered(key, rows + extra_rows, queries, dim,
                                 n_clusters=n_clusters)
    return train[:rows], train[rows:], pool
