"""The work the coarse probe of an IVF index requires: each query's
squared distance to every centroid (a (Q, d) by (d, nlist) product) and
the ``nprobe`` nearest kept. What it reads: the centroids once a call, the
queries, and the probed cell ids it writes."""
from __future__ import annotations

import re

__all__ = ["probe_shape", "probe_work"]

_SPEC = re.compile(r"^(?:[a-z]+(\d+)>)?ivf(\d+)x(\d+)>")


def probe_shape(spec: str) -> tuple:
    """(nlist, nprobe, dim the probe runs in) of an index spec string such
    as ``qpad32>ivf16384x32>pq16x256>rr64``; the dim is the Reduce stage's
    (None where the spec has no Reduce stage)."""
    m = _SPEC.match(spec)
    if m is None:
        raise ValueError(f"no coarse stage in spec {spec!r}")
    dim, nlist, nprobe = m.groups()
    return int(nlist), int(nprobe), None if dim is None else int(dim)


def probe_work(queries: int, calls: int, nlist: int, nprobe: int,
               dim: int) -> dict:
    """Bytes and operations of the probe for ``queries`` queries served in
    ``calls`` calls: 2·dim operations per query and centroid; the (nlist,
    dim) f32 centroids read once a call, the (queries, dim) f32 queries
    read and the (queries, nprobe) int32 probed ids written."""
    return {"bytes": calls * nlist * dim * 4 + queries * dim * 4
            + queries * nprobe * 4,
            "flops": 2 * queries * nlist * dim}
