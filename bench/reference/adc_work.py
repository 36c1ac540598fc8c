"""The work an IVF-PQ ADC scan requires, counted from the probed posting
mass: the rows in the ``nprobe`` cells that the coarse probe picks for each
query, never the padded ``nprobe * max_cell`` width an implementation may
choose to gather. The byte terms follow ``adc_scan_bytes`` in the
program's ``benchmarks/roofline.py`` (code bytes, an id and a base term per
candidate, the per-query lookup table); this copy reads the table once and
writes no score, which is what the algorithm itself needs.
"""
from __future__ import annotations

import numpy as np

__all__ = ["probed_rows", "adc_work", "rerank_work"]

LUT_BYTES = {"f32": 4, "bf16": 2, "int8": 1}


def probed_rows(reduced_queries, centroids, cell_sizes, nprobe: int
                ) -> np.ndarray:
    """Rows each query's ``nprobe`` nearest cells hold (a float64 numpy
    probe of its own, so the count does not depend on the program's).
    ``reduced_queries`` (Q, d), ``centroids`` (nlist, d), ``cell_sizes``
    (nlist,) live rows per cell. Returns (Q,) int64."""
    q = np.asarray(reduced_queries, np.float64)
    c = np.asarray(centroids, np.float64)
    d2 = (np.sum(q * q, 1)[:, None] + np.sum(c * c, 1)[None, :]
          - 2.0 * q @ c.T)
    probe = np.argpartition(d2, nprobe - 1, axis=1)[:, :nprobe]
    return np.asarray(cell_sizes, np.int64)[probe].sum(axis=1)


def adc_work(probed: int, queries: int, m: int, kc: int, d_reduced: int,
             lut_dtype: str = "f32", code_bytes: int = 1) -> dict:
    """Bytes and operations the ADC scan needs for ``queries`` queries that
    probe ``probed`` rows in all: per candidate its ``m`` code bytes, an
    int32 id and an f32 base term read, and ``m`` table adds; per query one
    read of the (m, kc) table and the matmul that builds it (2·d·m·kc)."""
    lut = queries * m * kc * LUT_BYTES[lut_dtype]
    per_row = m * code_bytes + 4 + 4
    return {"bytes": probed * per_row + lut,
            "flops": probed * m + queries * 2 * d_reduced * m * kc}


def rerank_work(queries: int, candidates: int, dim: int) -> dict:
    """The exact re-rank's work: per query, ``candidates`` f32 rows of
    ``dim`` and their ids read, and 3·dim operations a row (subtract,
    square, add)."""
    return {"bytes": queries * candidates * (dim * 4 + 4),
            "flops": queries * candidates * 3 * dim}
