"""Jit'd wrapper: MPAD objective value-and-grad backed by the Pallas kernel.

Hybrid schedule (DESIGN.md §3.2): the b%-quantile threshold tau_b is found on
the *sorted scalar projections* (O(N log N) — sorting N scalars is trivial
next to the N^2 pair pass), then ONE kernel pass produces the exact count /
sum / gradient coefficients. This keeps the expensive O(N^2) work in a single
tiled VMEM-resident sweep instead of the ~60 sweeps a count-only bisection
would need.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.fast_objective import find_quantile_threshold
from repro.core.objective import num_selected_pairs
from .kernel import pairwise_stats_pallas


@functools.partial(jax.jit, static_argnames=("b", "interpret", "block"))
def mu_kernel_value_and_grad(w: jax.Array, x: jax.Array, *, b: float,
                             interpret: bool | None = None, block: int = 256):
    """Value and tangent gradient of mu_b at unit ``w`` via the Pallas kernel."""
    k_pairs = num_selected_pairs(x.shape[0], b)
    wn = w / jnp.linalg.norm(w)
    p = x @ wn
    tau = find_quantile_threshold(p, k_pairs)
    cnt, s, coeff = pairwise_stats_pallas(
        p, tau, block_i=block, block_j=block, interpret=interpret)
    cntf = jnp.maximum(cnt, 1).astype(p.dtype)
    excess = cntf - k_pairs
    value = (s - excess * tau) / k_pairs
    g_raw = (x.T @ coeff) / cntf
    g = g_raw - jnp.dot(g_raw, wn) * wn
    return value, g


@functools.partial(jax.jit, static_argnames=("b", "alpha", "interpret", "block"))
def phi_kernel_value_and_grad(w, x, prev, prev_mask, *, b: float, alpha: float,
                              interpret: bool | None = None, block: int = 256):
    """Trainer backend contract (see repro.core.mpad._get_backend)."""
    mu, g_mu = mu_kernel_value_and_grad(w, x, b=b, interpret=interpret,
                                        block=block)
    wn = w / jnp.linalg.norm(w)
    dots = (prev @ wn) * prev_mask
    pen = alpha * jnp.sum(dots * dots)
    g_pen_raw = 2.0 * alpha * (prev.T @ (dots * prev_mask))
    g_pen = g_pen_raw - jnp.dot(g_pen_raw, wn) * wn
    return mu - pen, g_mu - g_pen
