"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

The search-side kernels (``pq_adc``, ``knn_topk``, ``mpad_pairwise``) take
``interpret=None`` and resolve it here, at trace time, from the platform
JAX runs on. No serving knob chooses the mode: a TPU always gets the
compiled Mosaic kernel and a CPU host always gets the Pallas interpreter.
Callers that must force one mode (the
compile-only tests, which lower for a described TPU from a CPU host) pass
an explicit bool.
"""
from __future__ import annotations

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret unless the default backend is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
