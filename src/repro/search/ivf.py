"""IVF-Flat approximate nearest-neighbor index (k-means coarse quantizer).

The paper's future-work item "integrating MPAD into existing ANN pipelines":
vectors (optionally MPAD-reduced) are clustered into ``nlist`` cells; a query
probes the ``nprobe`` nearest cells and scans only those posting lists.

Implementation is padded-dense for jit-ability: each cell's posting list is a
fixed-size row of a (nlist, max_cell) index matrix (padded with -1), so the
probe-scan is a gather + masked top-k — the TPU-idiomatic layout (no ragged
structures on device).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .knn import masked_topk
from .tracing import span

__all__ = ["BUILD_TILE_BYTES", "IVFIndex", "assign_cells", "balance_cells",
           "block_rows", "build_ivf", "cell_vectors", "ivf_local_scan",
           "ivf_scan", "ivf_search", "kmeans", "kmeans_ref",
           "map_row_blocks", "posting_lists", "probe_cells", "sq_dists"]

# Bytes of the (rows, k) float32 distance tile that one row block of the
# index build computes against k centroids: k-means, the cell assignment
# and PQ encoding walk the corpus in blocks of ``block_rows(n, k)`` rows,
# so set-up memory is set by this tile and not by N x k.
BUILD_TILE_BYTES = 1 << 28


def sq_dists(a: jax.Array, b: jax.Array) -> jax.Array:
    """Unclamped pairwise squared L2: |a|^2 + |b|^2 - 2 a@b^T, shape (A, B)."""
    return (jnp.sum(a * a, 1)[:, None] + jnp.sum(b * b, 1)[None, :]
            - 2.0 * a @ b.T)


class IVFIndex(NamedTuple):
    centroids: jax.Array    # (nlist, d)
    lists: jax.Array        # (nlist, max_cell) int32 vector ids, -1 = pad
    vectors: jax.Array      # (N, d) the stored (possibly reduced) vectors


def block_rows(n: int, k: int) -> int:
    """Rows of one build block against ``k`` centroids: as many as keep the
    (rows, k) f32 distance tile within ``BUILD_TILE_BYTES`` (a multiple of
    8, at least 8), and never more than ``n``."""
    rows = max(8, BUILD_TILE_BYTES // (4 * k) // 8 * 8)
    return max(1, min(n, rows))


def _block_start(b, n: int, block: int):
    """Row where block ``b`` starts: the last block is taken back to end at
    row ``n``, so no block reads past the array and nothing is padded."""
    return jnp.minimum(b * block, n - block)


def map_row_blocks(fn, n: int, block: int, *arrays):
    """``fn`` over row blocks of ``arrays`` (each (n, ...)), its outputs
    ((block, ...) arrays, or a tuple of them) written into (n, ...) arrays.

    One ``fn`` call per block in a ``fori_loop``. The last block ends at row
    ``n``, so the rows it shares with the block before are computed twice
    from the same inputs and written again: ``fn`` must treat rows
    independently.
    """
    shapes = jax.eval_shape(fn, *(jax.ShapeDtypeStruct(
        (block,) + a.shape[1:], a.dtype) for a in arrays))
    init = jax.tree.map(
        lambda s: jnp.zeros((n,) + s.shape[1:], s.dtype), shapes)

    def body(b, outs):
        start = _block_start(b, n, block)
        res = fn(*(jax.lax.dynamic_slice_in_dim(a, start, block)
                   for a in arrays))
        return jax.tree.map(
            lambda o, r: jax.lax.dynamic_update_slice_in_dim(o, r, start, 0),
            outs, res)

    return jax.lax.fori_loop(0, -(-n // block), body, init)


@functools.partial(jax.jit, static_argnames=("block",))
def _assign_cells(x: jax.Array, cent: jax.Array, block: int) -> jax.Array:
    return map_row_blocks(
        lambda xb: jnp.argmin(sq_dists(xb, cent), axis=1).astype(jnp.int32),
        x.shape[0], block, x)


def assign_cells(x: jax.Array, cent: jax.Array) -> jax.Array:
    """Nearest centroid of every row: (N,) int32, the argmin of
    ``sq_dists`` taken in row blocks of ``block_rows``."""
    return _assign_cells(x, cent, block_rows(x.shape[0], cent.shape[0]))


@functools.partial(jax.jit, static_argnames=("block",))
def _lloyd_step(x: jax.Array, cent: jax.Array, block: int) -> jax.Array:
    """One Lloyd step over all rows of ``x``, in row blocks: distances,
    argmin, counts and sums per block, accumulated."""
    n, d = x.shape
    nlist = cent.shape[0]
    rows = jnp.arange(block)

    def body(b, acc):
        sums, counts = acc
        start = _block_start(b, n, block)
        xb = jax.lax.dynamic_slice_in_dim(x, start, block)
        assign = jnp.argmin(sq_dists(xb, cent), axis=1)
        # rows the block before already counted go to no cluster
        assign = jnp.where(start + rows >= b * block, assign, nlist)
        one_hot = jax.nn.one_hot(assign, nlist, dtype=x.dtype)
        return sums + one_hot.T @ xb, counts + one_hot.sum(0)

    sums, counts = jax.lax.fori_loop(
        0, -(-n // block), body,
        (jnp.zeros((nlist, d), x.dtype), jnp.zeros((nlist,), x.dtype)))
    new = sums / jnp.maximum(counts, 1.0)[:, None]
    # keep old centroid for empty clusters
    return jnp.where((counts > 0)[:, None], new, cent)


@functools.partial(jax.jit, static_argnames=("nlist",))
def _kmeans_init(key: jax.Array, x: jax.Array, nlist: int) -> jax.Array:
    return x[jax.random.choice(key, x.shape[0], (nlist,), replace=False)]


def kmeans(key: jax.Array, x: jax.Array, nlist: int, iters: int = 12):
    """Lloyd k-means with k-means++-ish random restarts on empty clusters.

    ``kmeans_ref``'s algorithm over all N rows, with each step's distances,
    argmin, counts and sums taken in row blocks of ``block_rows(N, nlist)``
    and accumulated, so no (N, nlist) array is made. Each step is its own
    program: inside one loop over the steps the TPU compiler re-lays ``x``
    out row-major, which pads a (N, 32) f32 corpus to four times its size.
    """
    block = block_rows(x.shape[0], nlist)
    cent = _kmeans_init(key, x, nlist)
    for _ in range(iters):
        cent = _lloyd_step(x, cent, block)
    return cent


@functools.partial(jax.jit, static_argnames=("nlist", "iters"))
def kmeans_ref(key: jax.Array, x: jax.Array, nlist: int, iters: int = 12):
    """``kmeans`` over the whole corpus at once: (N, nlist) distances and
    one-hot in every step. The plain reference the blocked build is tested
    against."""
    n = x.shape[0]
    init = jax.random.choice(key, n, (nlist,), replace=False)
    cent = x[init]

    def step(cent, _):
        assign = jnp.argmin(sq_dists(x, cent), axis=1)
        one_hot = jax.nn.one_hot(assign, nlist, dtype=x.dtype)
        counts = one_hot.sum(0)
        sums = one_hot.T @ x
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # keep old centroid for empty clusters
        new = jnp.where((counts > 0)[:, None], new, cent)
        return new, None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    return cent


def posting_lists(assign: jax.Array, nlist: int, shards: int = 1) -> jax.Array:
    """Padded-dense posting lists from a cell assignment.

    Returns (nlist_pad, max_cell) int32 vector ids, -1 = pad; rows are
    cells. ``shards`` pads the cell axis up to a multiple with empty
    (all -1) cells so the layout splits into per-shard-equal blocks along
    the database axis (sharded serving); shards=1 leaves it unchanged.
    Padded cells are unreachable: the coarse probe only ever emits real
    cell ids (< nlist).
    """
    counts = jnp.bincount(assign, length=nlist)
    max_cell = int(counts.max())
    nlist_pad = -(-nlist // shards) * shards
    # stable bucket layout: sort ids by (cell, id); row-major fill
    order = jnp.argsort(assign, stable=True)
    sorted_cells = assign[order]
    # position of each sorted element within its cell
    pos = jnp.arange(order.shape[0]) - jnp.searchsorted(
        sorted_cells, sorted_cells, side="left")
    lists = jnp.full((nlist_pad, max_cell), -1, jnp.int32)
    return lists.at[sorted_cells, pos].set(order.astype(jnp.int32))


def balance_cells(counts, shards: int) -> np.ndarray:
    """Load-aware cell placement: a permutation of the cell axis such that
    the per-shard contiguous blocks carry near-equal posting-list **mass**
    (row count), not just equal cell count.

    Greedy LPT bin-pack: cells sorted heaviest-first, each placed on the
    lightest shard that still has cell slots. Shard s's slot budget is the
    block size ``ceil(nlist / shards)``, except the tail blocks that the
    ``posting_lists`` padding turns into all-pad cells (pads stay at the
    end of the cell axis, which the sharded layout relies on). Host-side
    (build time, numpy); apply the permutation to centroids AND the
    assignment so cell ids stay consistent end to end.
    """
    counts = np.asarray(counts)
    nlist = counts.shape[0]
    per = -(-nlist // shards)
    caps = np.full(shards, per)
    deficit = per * shards - nlist
    s = shards - 1
    while deficit > 0:                     # pad cells live in the tail blocks
        take = min(per, deficit)
        caps[s] -= take
        deficit -= take
        s -= 1
    order = np.argsort(-counts, kind="stable")
    load = np.zeros(shards, dtype=np.int64)
    members: list = [[] for _ in range(shards)]
    for c in order:
        elig = [i for i in range(shards) if len(members[i]) < caps[i]]
        tgt = min(elig, key=lambda i: (load[i], i))
        members[tgt].append(int(c))
        load[tgt] += int(counts[c])
    return np.concatenate(
        [np.asarray(m, dtype=np.int64) for m in members if m])


def _balanced_layout(cent: jax.Array, assign: jax.Array, nlist: int,
                     shards: int):
    """Permute the cell axis by ``balance_cells`` (centroid order is
    arbitrary, so this changes layout only, never scan results)."""
    counts = np.asarray(jnp.bincount(assign, length=nlist))
    perm = balance_cells(counts, shards)
    inv = np.empty(nlist, np.int32)
    inv[perm] = np.arange(nlist, dtype=np.int32)
    return cent[jnp.asarray(perm)], jnp.asarray(inv)[assign]


def build_ivf(key: jax.Array, vectors: jax.Array, nlist: int,
              kmeans_iters: int = 12, shards: int = 1,
              balance: bool = True) -> IVFIndex:
    """``balance`` (with ``shards > 1``) permutes cells so shard blocks
    carry near-equal posting mass — see ``balance_cells``."""
    vectors = jnp.asarray(vectors, jnp.float32)
    with span("qpad.build.coarse"):
        cent = kmeans(key, vectors, nlist, kmeans_iters)
    with span("qpad.build.assign"):
        assign = assign_cells(vectors, cent)              # (N,)
    with span("qpad.build.layout"):
        if balance and shards > 1:
            cent, assign = _balanced_layout(cent, assign, nlist, shards)
        lists = posting_lists(assign, nlist, shards)
    return IVFIndex(centroids=cent, lists=lists, vectors=vectors)


def cell_vectors(lists: jax.Array, vectors: jax.Array) -> jax.Array:
    """Cell-major mirror of the stored vectors: (nlist, max_cell, d).

    Posting pads (-1) become zero rows. Probe-time access turns into nprobe
    contiguous row-block gathers, and — like ``codes_cell`` in IVF-PQ — the
    cell axis is the database axis sharded serving partitions.
    """
    cv = vectors[jnp.maximum(lists, 0)]
    return jnp.where((lists >= 0)[..., None], cv, 0.0)


def probe_cells(centroids: jax.Array, lists: jax.Array, q: jax.Array,
                nprobe: int, min_cand: int):
    """Shared coarse-probe: nearest ``nprobe`` cells' posting lists.

    Returns (probe (Q, nprobe) int32 cell ids, cand (Q, C) int32 vector ids
    with -1 pads, coarse_d2 (Q, nprobe) squared distances to the probed
    centroids, in probe order). ``cand`` is right-padded with -1 up to
    ``min_cand`` so a downstream top-k of that size is always legal
    (degenerate probe budgets). Pure/unjitted so callers can inline it into
    larger fused programs; ``probe`` lets them gather any cell-major
    per-vector payload (codes, bias, vectors) with contiguous row gathers.
    """
    with jax.named_scope("qpad.probe"):
        cd2 = sq_dists(q, centroids)                      # (Q, nlist)
        _, probe = jax.lax.top_k(-cd2, nprobe)            # (Q, nprobe)
        cd2p = jnp.take_along_axis(cd2, probe, axis=1)    # (Q, nprobe)
    cand = lists[probe].reshape(q.shape[0], -1)           # (Q, nprobe*max_cell)
    if cand.shape[1] < min_cand:
        cand = jnp.pad(cand, ((0, 0), (0, min_cand - cand.shape[1])),
                       constant_values=-1)
    return probe, cand, cd2p


def ivf_scan(index: IVFIndex, q: jax.Array, k: int, nprobe: int = 8):
    """Unjitted ``ivf_search`` core (inlineable into fused programs)."""
    q = jnp.asarray(q, jnp.float32)
    cent, lists, vecs = index
    _, cand, _ = probe_cells(cent, lists, q, nprobe, k)
    valid = cand >= 0
    cv = vecs[jnp.maximum(cand, 0)]                       # (Q, C, d)
    d2 = jnp.sum((cv - q[:, None, :]) ** 2, axis=-1)
    d2 = jnp.where(valid, d2, jnp.inf)
    neg, sel = jax.lax.top_k(-d2, k)
    ids = jnp.take_along_axis(cand, sel, axis=1)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), ids


def ivf_local_scan(centroids: jax.Array, lists_loc: jax.Array,
                   cell_vecs_loc: jax.Array, q: jax.Array, n_cand: int,
                   nprobe: int, axis: str,
                   live: Optional[jax.Array] = None):
    """Shard-local IVF probe + scan (a ``shard_map`` body of sharded serving).

    The coarse probe runs on the replicated ``centroids`` — identical on
    every shard, so it equals the single-device probe exactly — then only
    the probed cells this shard owns (rows of ``lists_loc``/
    ``cell_vecs_loc``, offset by ``axis_index * nlist_local`` along the
    cell axis) are scanned. Returns (d2 (Q, n_cand), global ids (Q,
    n_cand)); non-local or padded slots are (+inf, -1) and are supplied by
    the shard that owns them. ``live`` (replicated (N,) bool, streaming
    serving) additionally masks tombstoned/unallocated global rows before
    the local top-k, so dead rows never crowd out live candidates.
    """
    q = jnp.asarray(q, jnp.float32)
    with jax.named_scope("qpad.probe"):
        cd2 = sq_dists(q, centroids)                      # (Q, nlist)
        _, probe = jax.lax.top_k(-cd2, nprobe)            # global cell ids
    nl_loc = lists_loc.shape[0]
    coff = jax.lax.axis_index(axis) * nl_loc
    lp = probe - coff
    own = (lp >= 0) & (lp < nl_loc)                       # (Q, nprobe)
    lpc = jnp.clip(lp, 0, nl_loc - 1)
    cand = jnp.where(own[:, :, None], lists_loc[lpc], -1)
    if live is not None:
        n_cap = live.shape[0]
        cand = jnp.where(live[jnp.clip(cand, 0, n_cap - 1)], cand, -1)
    cv = cell_vecs_loc[lpc]                               # (Q, P, mc, d)
    d2 = jnp.sum((cv - q[:, None, None, :]) ** 2, axis=-1)
    nq = q.shape[0]
    cand = cand.reshape(nq, -1)
    d2 = jnp.where(cand >= 0, d2.reshape(nq, -1), jnp.inf)
    return masked_topk(d2, cand, n_cand)


@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def ivf_search(index: IVFIndex, q: jax.Array, k: int, nprobe: int = 8):
    """Probe the nprobe nearest cells; returns (dists (Q,k), ids (Q,k))."""
    return ivf_scan(index, q, k, nprobe)
