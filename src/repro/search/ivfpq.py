"""IVF-PQ: coarse k-means quantizer + product-quantized **residuals** —
the classic memory-hierarchy composition for production vector search
(reduce dims -> coarse-quantize -> PQ-code what the centroid missed).

Layout matches ``ivf.py``: padded-dense posting lists (nlist, max_cell)
with -1 pads, so probe-scan is gather + masked top-k (TPU-idiomatic, no
ragged structures on device). Codebooks are trained on residuals
``x - centroid[assign(x)]`` and shared across cells (standard IVF-ADC).

Scoring uses the exact residual decomposition so the per-query LUT is
cell-independent — the same (Q, M, K) shape as plain PQ, which is what lets
the fused ADC kernel serve both index types. With reconstruction
x̂ = c + r̂, r̂_m = cb[m, code_m]:

  ||q - x̂||² = ||q - c||²                                   (coarse term,
                                                 already computed to probe)
             + Σ_m ( ||cb[m,code_m]||² - 2⟨q_m, cb[m,code_m]⟩ )   (query LUT)
             + 2 Σ_m ⟨c_m, cb[m,code_m]⟩                 (per-id build-time
                                                          scalar: ``bias``)

No approximation beyond PQ itself: the cross terms are exact.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.pq_adc.ref import (pq_adc_gather_scores_onehot,
                                      pq_adc_gather_scores_ref)
from .ivf import (_balanced_layout, block_rows, kmeans, map_row_blocks,
                  posting_lists, probe_cells, sq_dists)
from .pq import (_check_adc_args, _code_dtype, _encode_block_rows,
                 _nearest_codes, adc_tables, lut_projection, train_codebooks)
from .tracing import span

__all__ = ["IVFPQIndex", "build_ivfpq", "encode_residuals", "ivfpq_adc_scan",
           "ivfpq_compact_scan", "ivfpq_local_scan", "ivfpq_lut_stats",
           "ivfpq_scan", "ivfpq_search"]


class IVFPQIndex(NamedTuple):
    centroids: jax.Array    # (nlist, d) coarse quantizer
    lists: jax.Array        # (nlist, max_cell) int32 vector ids, -1 = pad
    codebooks: jax.Array    # (M, K, dsub) residual-space PQ codebooks
    codes: jax.Array        # (N, M) uint8/int32 residual codes, id-aligned
    bias: jax.Array         # (N,) f32: 2·Σ_m ⟨cent[assign]_m, cb[m, code_m]⟩
    rerr: jax.Array         # (N,) f32 per-row PQ reconstruction error
                            # ||x - x̂||, the exact-distance bound used by
                            # the re-rank candidate pre-filter
    # cell-major serving mirrors of codes/bias: probe-time access becomes
    # nprobe contiguous row-block gathers instead of |cand| scattered ones
    codes_cell: jax.Array   # (nlist, max_cell, M) uint8 (int32 if K > 256)
    bias_cell: jax.Array    # (nlist, max_cell) f32, 0 on pads
    lut_w: jax.Array        # (d, M*K) block-diagonal -2*codebook projection
    cbnorm: jax.Array       # (M, K) residual codeword squared norms


@functools.partial(jax.jit, static_argnames=("block",))
def _assign_residuals(x, cent, block):
    """Nearest centroid of each row and the row's residual to it, in row
    blocks: ((N,) int32, (N, d) f32)."""
    def blk(xb):
        a = jnp.argmin(sq_dists(xb, cent), axis=1).astype(jnp.int32)
        return a, xb - cent[a]
    return map_row_blocks(blk, x.shape[0], block, x)


def encode_residuals(codebooks, cent, residuals, assign):
    """Codes (int32) of residual rows whose cells are ``assign``, with each
    row's centroid/codeword cross term ``bias`` and reconstruction error
    ``rerr`` (module docstring): the one encoding of build time and of
    compaction, so a row gets the same payload from either."""
    m, _, dsub = codebooks.shape
    codes = _nearest_codes(codebooks, residuals)          # (B, M)
    recon = jnp.take_along_axis(
        codebooks[None], codes[:, :, None, None], axis=2
    )[:, :, 0, :]                                         # (B, M, dsub)
    csub = cent[assign].reshape(-1, m, dsub)
    bias = 2.0 * jnp.sum(csub * recon, axis=(1, 2))
    rerr = jnp.sqrt(jnp.sum(
        (residuals - recon.reshape(residuals.shape)) ** 2, axis=1))
    return codes, bias, rerr


@functools.partial(jax.jit, static_argnames=("block", "code_dt"))
def _encode_blocks(codebooks, cent, residuals, assign, block, code_dt):
    """``encode_residuals`` in row blocks, codes stored as ``code_dt``."""
    def blk(rb, ab):
        codes, bias, rerr = encode_residuals(codebooks, cent, rb, ab)
        return codes.astype(code_dt), bias, rerr
    return map_row_blocks(blk, residuals.shape[0], block, residuals, assign)


@functools.partial(jax.jit, static_argnames=("block", "code_dt"))
def _cell_mirrors(lists, codes, bias, block, code_dt):
    """Cell-major ``codes_cell`` and ``bias_cell`` in blocks of cells."""
    def blk(lb):
        lid = jnp.maximum(lb, 0)
        return (codes[lid].astype(code_dt),
                jnp.where(lb >= 0, bias[lid], 0.0).astype(jnp.float32))
    return map_row_blocks(blk, lists.shape[0], block, lists)


def build_ivfpq(key: jax.Array, vectors: jax.Array, nlist: int,
                m_subspaces: int = 8, n_centroids: int = 256,
                kmeans_iters: int = 12, pq_iters: int = 10,
                shards: int = 1, balance: bool = True) -> IVFPQIndex:
    """Coarse k-means, then per-subspace codebooks on the residuals.

    Every pass over the rows (k-means, assignment, codebook training,
    encoding) runs in row blocks (``ivf.block_rows``), so no (N, nlist) or
    (N, K) array is made and set-up fits where the index does.

    ``shards`` pads the cell axis of the cell-major serving mirrors
    (``lists``/``codes_cell``/``bias_cell``) to per-shard-equal shapes
    (see ``posting_lists``); ``balance`` additionally permutes the cell
    axis so the per-shard blocks carry near-equal posting **mass**
    (``repro.search.ivf.balance_cells`` — the load-aware placement for
    skewed corpora). Quantization and scan results are unchanged either
    way.
    """
    vectors = jnp.asarray(vectors, jnp.float32)
    n = vectors.shape[0]
    with span("qpad.build.coarse"):
        cent = kmeans(key, vectors, nlist, kmeans_iters)
    with span("qpad.build.assign"):
        assign, residuals = _assign_residuals(vectors, cent,
                                              block_rows(n, nlist))
    with span("qpad.build.codebooks"):
        cbs = train_codebooks(jax.random.fold_in(key, 7), residuals,
                              m_subspaces, n_centroids, pq_iters)
    with span("qpad.build.encode"):
        codes, bias, rerr = _encode_blocks(
            cbs, cent, residuals, assign, _encode_block_rows(n, cbs),
            _code_dtype(n_centroids))
    del residuals
    with span("qpad.build.layout"):
        # the permutation moves cells only: residuals and bias are unchanged
        if balance and shards > 1:
            cent, assign = _balanced_layout(cent, assign, nlist, shards)
        lists = posting_lists(assign, nlist, shards)
        nl, max_cell = lists.shape
        codes_cell, bias_cell = _cell_mirrors(
            lists, codes, bias, block_rows(nl, max_cell * m_subspaces),
            _code_dtype(cbs.shape[1]))
    lut_w, cbnorm = lut_projection(cbs)
    return IVFPQIndex(centroids=cent, lists=lists, codebooks=cbs,
                      codes=codes, bias=bias, rerr=rerr,
                      codes_cell=codes_cell, bias_cell=bias_cell,
                      lut_w=lut_w, cbnorm=cbnorm)


def _adc_lowering() -> str:
    """How the jnp backend scores gathered candidates, resolved at trace
    time from the platform (as ``kernels.platform.resolve_interpret``
    resolves the kernels' mode): a one-hot select-reduce on a TPU, whose
    compiler lowers the table gather to an element gather four orders of
    magnitude off its roofline; the gather elsewhere, since on XLA:CPU it
    is fast and the one-hot does K times its arithmetic."""
    return "onehot" if jax.default_backend() == "tpu" else "gather"


def _adc_scores(tables, ccodes, base, lut_dtype, scale, center):
    """``pq_adc_gather_scores_ref``'s contract in the lowering
    ``_adc_lowering`` picks, under a scope that names it in traces."""
    lowering = _adc_lowering()
    score = (pq_adc_gather_scores_onehot if lowering == "onehot"
             else pq_adc_gather_scores_ref)
    with jax.named_scope(f"qpad.adc_{lowering}"):
        return score(tables, ccodes, base, lut_dtype, scale, center)


def ivfpq_lut_stats(codebooks: jax.Array, cbnorm: jax.Array, q: jax.Array,
                    lut_dtype: str):
    """Analytic centering + certified int8 scale for the quantized LUT.

    The old path centered the computed (Q, M, K) tables empirically
    (``center_lut``) and, for int8, took ``max|t|`` over the whole table —
    two full-table reductions per batch. Both follow analytically from the
    codebook geometry instead, at O(M * K * dsub) cost (the codebooks are
    ~100x smaller than a serving batch's tables):

      t[q, m, k]  = cbnorm[m, k] - 2 <q_m, cb[m, k]>
      rowmean[q, m] = mean_k t[q, m, :]
                    = mean_k cbnorm[m, :] - 2 <q_m, mean_k cb[m, :]>

    and with ``t_c = t - rowmean`` (the part the grid has to cover),

      |t_c[q, m, k]| <= max_k|cbnorm_c[m, :]| + ||q_m|| * max_k||-2 cb_c[m, k]||

    by Cauchy-Schwarz on the centered codewords — a certified bound, so the
    int8 grid built from it never clips. The tiny (1 + 1e-5) headroom
    absorbs the f32 rounding of ``t`` itself.

    [measured trade, don't "fix" either way without re-measuring both: the
    bound runs ~1.4-1.9x looser than the true ``max|t_c|``, which costs
    nothing on the bench corpus (recall gate) but ~0.05 recall@10 on a
    heavy-cluster corpus whose ADC gaps are comparable to the grid step;
    the exact scale (abs-max over the materialized tables, or min/max per
    row — both tried) re-reads the (Q, M, K) tables and costs ~13% of int8
    scan throughput on CPU, failing the int8 >= 0.95x-of-f32 QPS gate. A
    per-codeword Cauchy-Schwarz bound is no tighter on exactly the corpora
    that hurt and costs as much as the exact pass.]

    Returns (rowmean (Q, M) f32, scale (Q,) f32 or None when ``lut_dtype``
    needs no scale). Centering any fixed per-(q, m) constant is exact —
    the ADC sum restores ``sum_m rowmean`` through the f32 ``base`` term —
    so the analytic mean does not need to match the empirical one.
    """
    nq = q.shape[0]
    m, kc = cbnorm.shape
    dsub = codebooks.shape[2]
    qs = q.reshape(nq, m, dsub)
    wmean = -2.0 * jnp.mean(codebooks, axis=1)            # (M, dsub)
    cbmean = jnp.mean(cbnorm, axis=1)                     # (M,)
    rowmean = cbmean[None] + jnp.einsum("qmd,md->qm", qs, wmean)
    if lut_dtype != "int8":
        return rowmean, None
    w_c = -2.0 * codebooks - wmean[:, None, :]            # centered codewords
    wmax = jnp.max(jnp.sqrt(jnp.sum(w_c * w_c, axis=2)), axis=1)   # (M,)
    cbmax = jnp.max(jnp.abs(cbnorm - cbmean[:, None]), axis=1)     # (M,)
    qn = jnp.sqrt(jnp.sum(qs * qs, axis=2))               # (Q, M)
    bound = jnp.max(cbmax[None] + qn * wmax[None], axis=1) * (1.0 + 1e-5)
    return rowmean, jnp.maximum(bound, 1e-12) / 127.0


def ivfpq_adc_scan(centroids: jax.Array, lists: jax.Array,
                   codes_cell: jax.Array, bias_cell: jax.Array,
                   lut_w: jax.Array, cbnorm: jax.Array,
                   codebooks: jax.Array, q: jax.Array,
                   n_cand: int, nprobe: int = 8, backend: str = "jnp",
                   lut_dtype: str = "f32", live=None):
    """Probe + cell-major ADC scan over raw index arrays — the shared core
    of ``ivfpq_scan`` (read-only serving) and the streaming masked scan.

    ``live`` (optional (N,) bool keyed by row id) masks
    tombstoned/unallocated rows; like the posting-pad mask it rides the
    additive ``base`` term, so it works identically on both scoring
    backends. Returns (d2 (Q, n_cand) SQUARED approximate distances, ids
    (Q, n_cand)) with (+inf, -1) on masked/unfilled slots.
    """
    _check_adc_args(backend, lut_dtype)
    q = jnp.asarray(q, jnp.float32)
    # coarse probe: distances to every centroid, keep the nprobe nearest
    probe, cand, cd2p = probe_cells(centroids, lists, q,
                                    nprobe, n_cand)       # (Q,P),(Q,C),(Q,P)
    return ivfpq_scan_given_probe(probe, cand, cd2p, codes_cell, bias_cell,
                                  lut_w, cbnorm, codebooks, q, n_cand,
                                  backend=backend, lut_dtype=lut_dtype,
                                  live=live)


def ivfpq_scan_given_probe(probe: jax.Array, cand: jax.Array,
                           cd2p: jax.Array, codes_cell: jax.Array,
                           bias_cell: jax.Array, lut_w: jax.Array,
                           cbnorm: jax.Array, codebooks: jax.Array,
                           q: jax.Array, n_cand: int, backend: str = "jnp",
                           lut_dtype: str = "f32", live=None):
    """ADC scan given an already-computed coarse probe — the back half of
    ``ivfpq_adc_scan``.
    """
    q = jnp.asarray(q, jnp.float32)
    nq = q.shape[0]
    m, kc = cbnorm.shape
    # cell-independent query LUT over residual codebooks: (Q, M, K), ONE
    # dense matmul via the build-time block-diagonal factorization.
    # Only this LUT is quantized under lut_dtype; the coarse distance +
    # cross-term ``base`` stays f32 (it is O(1) memory, not a table).
    tables = adc_tables(lut_w, cbnorm, q)
    # candidate codes + bias through the cell-major mirrors: nprobe
    # contiguous (max_cell, M) row blocks per query, no scattered gather;
    # codes stay at stored width (uint8) — backends widen in-register
    max_cell = codes_cell.shape[1]
    ccodes = codes_cell[probe].reshape(nq, -1, m)
    base = (jnp.repeat(cd2p, max_cell, axis=1)
            + bias_cell[probe].reshape(nq, -1))           # (Q, P*max_cell)
    short = cand.shape[1] - base.shape[1]                 # degenerate budget
    if short:
        ccodes = jnp.pad(ccodes, ((0, 0), (0, short), (0, 0)))
        base = jnp.pad(base, ((0, 0), (0, short)))
    ok = cand >= 0                                        # mask posting pads
    if live is not None:
        ok &= live[jnp.clip(cand, 0, live.shape[0] - 1)]
    base = jnp.where(ok, base, jnp.inf)
    center = scale = None
    if lut_dtype == "int8":
        # analytic row-mean centering + certified int8 scale: the int8 grid
        # only has to cover the candidate-varying part of the table, with
        # no table-wide reduction. bf16 is NOT centered — its rounding
        # error is relative, so centering buys nothing and would cost the
        # stats einsum + an extra table pass. The omitted per-query
        # constant sum_m center is restored after top-k, where it touches
        # k values, not P*max_cell.
        center, scale = ivfpq_lut_stats(codebooks, cbnorm, q, lut_dtype)
    k_eff = min(n_cand, cand.shape[1])
    if backend == "kernel":
        from repro.kernels.pq_adc import pq_adc_gather_topk_pallas
        kt = tables if center is None else tables - center[:, :, None]
        d2, sel = pq_adc_gather_topk_pallas(kt, ccodes, base, k_eff,
                                            lut_dtype=lut_dtype, scale=scale)
    else:
        adc = _adc_scores(tables, ccodes, base, lut_dtype, scale, center)
        neg, sel = jax.lax.top_k(-adc, k_eff)
        d2 = -neg
    if center is not None:
        d2 = d2 + jnp.sum(center, axis=1)[:, None]        # inf pads stay inf
    # the kernel marks unfilled slots sel=-1; don't let them wrap the gather
    ids = jnp.where(sel >= 0,
                    jnp.take_along_axis(cand, jnp.maximum(sel, 0), axis=1),
                    -1)
    ids = jnp.where(jnp.isinf(d2), -1, ids)
    if k_eff < n_cand:
        d2 = jnp.pad(d2, ((0, 0), (0, n_cand - k_eff)),
                     constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, n_cand - k_eff)),
                      constant_values=-1)
    return d2, ids


def ivfpq_compact_scan(centroids: jax.Array, lists: jax.Array,
                       codes_cell: jax.Array, bias_cell: jax.Array,
                       lut_w: jax.Array, cbnorm: jax.Array,
                       codebooks: jax.Array, q: jax.Array,
                       n_cand: int, nprobe: int = 8, scan_cap: int = 128,
                       backend: str = "jnp", lut_dtype: str = "f32"):
    """nprobe-proportional ADC scan for small query buckets.

    The padded scan (``ivfpq_adc_scan``) gathers ``nprobe * max_cell``
    candidate slots per query regardless of how full the probed cells
    actually are; on skewed corpora most of those slots are -1 pads, and at
    small batch the wasted gather+score work dominates. This variant sizes
    work by actual posting mass instead: per-query prefix sums over the
    probed cell lengths map a flat slot ``j < scan_cap`` to (cell, in-cell
    slot), so only the first ``Σ len(probe_i)`` slots carry real candidates
    and the gather width is the **static** cap, not ``nprobe * max_cell``.

    Relies on the packed-prefix invariant of ``posting_lists`` /
    ``compact_fn``: every list row holds its real ids in slots
    ``[0, count)`` followed by -1 pads. Candidates are enumerated
    probe-major in in-cell slot order — exactly the padded scan's order
    minus the pads — so ``top_k`` tie-breaking (lowest index first) picks
    the same ids and the result is bit-identical to ``ivfpq_adc_scan``
    whenever ``scan_cap`` covers each query's probed mass (the engine
    guarantees this: cap = total mass of the ``nprobe`` largest cells).
    """
    _check_adc_args(backend, lut_dtype)
    if scan_cap <= 0:
        raise ValueError("ivfpq_compact_scan needs scan_cap > 0")
    q = jnp.asarray(q, jnp.float32)
    nq = q.shape[0]
    m, kc = cbnorm.shape
    with jax.named_scope("qpad.probe"):
        cd2 = sq_dists(q, centroids)                      # (Q, nlist)
        _, probe = jax.lax.top_k(-cd2, nprobe)            # probe_cells order
        cd2p = jnp.take_along_axis(cd2, probe, axis=1)
    tables = adc_tables(lut_w, cbnorm, q)
    lens = jnp.sum(lists >= 0, axis=1).astype(jnp.int32)  # (nlist,) mass
    plens = lens[probe]                                   # (Q, P)
    cum = jnp.cumsum(plens, axis=1)                       # inclusive
    start = cum - plens
    total = cum[:, -1:]
    j = jnp.arange(scan_cap, dtype=jnp.int32)[None, :]    # flat slots (1, S)
    # flat slot -> probe slot: first prefix sum strictly above j, i.e. the
    # count of prefix sums <= j. nprobe is small, so the (Q, P, S) compare
    # + sum beats a vmapped searchsorted (same result element for element)
    p = jnp.sum((cum[:, :, None] <= j[0][None, None, :]).astype(jnp.int32),
                axis=1)
    pc = jnp.clip(p, 0, nprobe - 1)
    cell = jnp.take_along_axis(probe, pc, axis=1)         # (Q, S)
    r = j - jnp.take_along_axis(start, pc, axis=1)        # in-cell slot
    rc = jnp.clip(r, 0, lists.shape[1] - 1)
    ok = j < total                                        # real posting mass
    cand = jnp.where(ok, lists[cell, rc], -1)
    ccodes = codes_cell[cell, rc]                         # (Q, S, M) uint8
    base = jnp.take_along_axis(cd2p, pc, axis=1) + bias_cell[cell, rc]
    base = jnp.where(cand >= 0, base, jnp.inf)
    center = scale = None
    if lut_dtype == "int8":
        # see ivfpq_adc_scan: int8-only analytic centering + certified
        # scale; the per-query constant is restored after top-k
        center, scale = ivfpq_lut_stats(codebooks, cbnorm, q, lut_dtype)
    k_eff = min(n_cand, scan_cap)
    if backend == "kernel":
        from repro.kernels.pq_adc import pq_adc_gather_topk_pallas
        kt = tables if center is None else tables - center[:, :, None]
        d2, sel = pq_adc_gather_topk_pallas(kt, ccodes, base, k_eff,
                                            lut_dtype=lut_dtype, scale=scale)
    else:
        adc = _adc_scores(tables, ccodes, base, lut_dtype, scale, center)
        neg, sel = jax.lax.top_k(-adc, k_eff)
        d2 = -neg
    if center is not None:
        d2 = d2 + jnp.sum(center, axis=1)[:, None]        # inf pads stay inf
    ids = jnp.where(sel >= 0,
                    jnp.take_along_axis(cand, jnp.maximum(sel, 0), axis=1),
                    -1)
    ids = jnp.where(jnp.isinf(d2), -1, ids)
    if k_eff < n_cand:
        d2 = jnp.pad(d2, ((0, 0), (0, n_cand - k_eff)),
                     constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, n_cand - k_eff)),
                      constant_values=-1)
    return d2, ids


def ivfpq_scan(index: IVFPQIndex, q: jax.Array, k: int, nprobe: int = 8,
               backend: str = "jnp", lut_dtype: str = "f32"):
    """Unjitted ``ivfpq_search`` core (inlineable into fused programs)."""
    d2, ids = ivfpq_adc_scan(index.centroids, index.lists, index.codes_cell,
                             index.bias_cell, index.lut_w, index.cbnorm,
                             index.codebooks, q, k, nprobe, backend,
                             lut_dtype)
    return jnp.sqrt(jnp.maximum(d2, 0.0)), ids


def ivfpq_local_scan(centroids: jax.Array, lists_loc: jax.Array,
                     codes_cell_loc: jax.Array, bias_cell_loc: jax.Array,
                     lut_w: jax.Array, cbnorm: jax.Array,
                     codebooks: jax.Array, q: jax.Array,
                     n_cand: int, nprobe: int, axis: str,
                     backend: str = "jnp", lut_dtype: str = "f32",
                     live=None):
    """Shard-local IVF-PQ probe + ADC scan (a ``shard_map`` body of sharded
    serving).

    The coarse probe and the per-query residual LUT both run on replicated
    inputs (centroids, ``lut_w``/``cbnorm``) so they are identical on every
    shard; only the probed cells this shard owns (rows of the cell-major
    mirrors, offset by ``axis_index * nlist_local``) are ADC-scored — the
    ``base`` of non-local or padded slots is +inf, which masks them through
    either scoring backend. ``live`` (replicated (N,) bool, streaming
    serving) masks tombstoned/unallocated rows the same way — riding the
    additive ``base`` term, so it works on both backends. Returns (d2 (Q,
    n_cand), global ids (Q, n_cand)) with (+inf, -1) on masked slots.
    """
    _check_adc_args(backend, lut_dtype)
    q = jnp.asarray(q, jnp.float32)
    nq = q.shape[0]
    m, kc = cbnorm.shape
    with jax.named_scope("qpad.probe"):
        cd2 = sq_dists(q, centroids)                      # (Q, nlist)
        _, probe = jax.lax.top_k(-cd2, nprobe)            # global cell ids
        cd2p = jnp.take_along_axis(cd2, probe, axis=1)
    tables = adc_tables(lut_w, cbnorm, q)
    nl_loc = lists_loc.shape[0]
    coff = jax.lax.axis_index(axis) * nl_loc
    lp = probe - coff
    own = (lp >= 0) & (lp < nl_loc)
    lpc = jnp.clip(lp, 0, nl_loc - 1)
    cand = jnp.where(own[:, :, None], lists_loc[lpc], -1).reshape(nq, -1)
    if live is not None:
        n_cap = live.shape[0]
        cand = jnp.where(live[jnp.clip(cand, 0, n_cap - 1)], cand, -1)
    ccodes = codes_cell_loc[lpc].reshape(nq, -1, m)
    base = (cd2p[:, :, None] + bias_cell_loc[lpc]).reshape(nq, -1)
    base = jnp.where(cand >= 0, base, jnp.inf)
    center = scale = None
    if lut_dtype == "int8":
        # replicated inputs -> identical centering/scale on every shard;
        # see ivfpq_adc_scan for the int8-only centering rationale
        center, scale = ivfpq_lut_stats(codebooks, cbnorm, q, lut_dtype)
    k_eff = min(n_cand, cand.shape[1])
    if backend == "kernel":
        from repro.kernels.pq_adc import pq_adc_gather_topk_pallas
        kt = tables if center is None else tables - center[:, :, None]
        d2, sel = pq_adc_gather_topk_pallas(kt, ccodes, base, k_eff,
                                            lut_dtype=lut_dtype, scale=scale)
    else:
        adc = _adc_scores(tables, ccodes, base, lut_dtype, scale, center)
        neg, sel = jax.lax.top_k(-adc, k_eff)
        d2 = -neg
    if center is not None:
        d2 = d2 + jnp.sum(center, axis=1)[:, None]        # inf stays inf
    ids = jnp.where(sel >= 0,
                    jnp.take_along_axis(cand, jnp.maximum(sel, 0), axis=1),
                    -1)
    ids = jnp.where(jnp.isinf(d2), -1, ids)
    if k_eff < n_cand:
        d2 = jnp.pad(d2, ((0, 0), (0, n_cand - k_eff)),
                     constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, n_cand - k_eff)),
                      constant_values=-1)
    return d2, ids


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "backend",
                                             "lut_dtype"))
def ivfpq_search(index: IVFPQIndex, q: jax.Array, k: int, nprobe: int = 8,
                 backend: str = "jnp", lut_dtype: str = "f32"):
    """Probe ``nprobe`` cells, ADC-score their residual codes, top-k.

    Returns (approx dists (Q, k), ids (Q, k)). ``backend="kernel"`` routes
    the candidate scoring through the fused Pallas ADC-gather kernel;
    ``lut_dtype`` quantizes the per-query residual LUT on either backend.
    """
    return ivfpq_scan(index, q, k, nprobe, backend, lut_dtype)
