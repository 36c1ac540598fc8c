"""Benchmark aggregator: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Fast subset by default
(suitable for CI); the full paper grids live in the per-figure modules:

  fig1_accuracy.py   — Fig.1 average A_m(k), all methods x datasets
  fig2_robustness.py — Fig.2 first/second-place counts over param grid
  fig3_ablation.py   — Fig.3 single-parameter ablations
  table3_scaling.py  — Table 3 runtime scaling vs N
  roofline.py        — §Roofline terms per dry-run cell

``--json [PATH]`` additionally writes ``BENCH_serve.json`` — the serving
perf trajectory (p50/p95 per query batch, QPS, recall@10 per index kind x
lut_dtype, the fused-vs-staged pipeline speedup, plus the reducer/index
``zoo`` grid: recall@10 + QPS per registered reducer x index spec); the
CSV output is unchanged. ``--fast`` runs only the serving + kernel subset (CI budget).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp


def _timeit(f, *args, reps=5, **kw):
    out = f(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = f(*args, **kw)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6          # us


def _timeit_dist(f, *args, reps=9, **kw):
    """Per-call wall times (us), warmed; for percentile reporting."""
    out = f(*args, **kw)
    jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = f(*args, **kw)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) * 1e6)
    return sorted(ts)


def _pctl(ts, p):
    return ts[min(len(ts) - 1, int(round(p / 100 * (len(ts) - 1))))]


def _timeit_interleaved(fns, reps=9, calls=1):
    """Per-variant wall times (us) with the variants time-sliced
    round-robin: every round times each variant once, in one process, so
    machine-load drift hits all variants equally and the cross-variant
    RATIOS the regression gates check stay trustworthy even when absolute
    numbers wander (single-core CI boxes drift 20-30% between time
    slices). ``fns`` is an ordered dict name -> nullary callable; every
    variant is warmed once before any timing. Returns name -> us in ROUND
    ORDER (same-index entries across variants are temporally adjacent, the
    alignment paired-ratio estimators need; sort for percentiles).
    ``calls`` > 1 times that many back-to-back invocations per turn and
    records the per-call mean — the first call after a variant switch
    runs with the other variant's working set still in cache, so
    averaging a short burst keeps the interleaving fair to BOTH variants
    instead of charging each one its neighbor's evictions. Callables may
    be stateful (each is invoked exactly ``reps * calls + 1`` times)."""
    for f in fns.values():
        jax.block_until_ready(f())
    ts = {name: [] for name in fns}
    for _ in range(reps):
        for name, f in fns.items():
            t0 = time.perf_counter()
            for _ in range(calls):
                jax.block_until_ready(f())
            ts[name].append((time.perf_counter() - t0) * 1e6 / calls)
    return ts


def bench_objective_backends(rows):
    """Table 3 (complexity): one objective eval, N=2048, n=64."""
    from repro.core.fast_objective import mu_b_fast_value_and_grad
    from repro.core.objective import mu_b_exact_value_and_grad
    from repro.kernels.mpad_pairwise import mu_kernel_value_and_grad
    n, d = 2048, 64
    x = jax.random.normal(jax.random.key(0), (n, d))
    w = jax.random.normal(jax.random.key(1), (d,))
    w = w / jnp.linalg.norm(w)
    us_fast = _timeit(mu_b_fast_value_and_grad, w, x, b=80.0)
    us_exact = _timeit(mu_b_exact_value_and_grad, w, x, b=80.0, reps=2)
    us_kern = _timeit(mu_kernel_value_and_grad, w, x, b=80.0, reps=2)
    rows.append(("mpad_objective_fast_N2048", us_fast,
                 f"speedup_vs_exact={us_exact / us_fast:.1f}x"))
    rows.append(("mpad_objective_exact_N2048", us_exact, "paper_faithful"))
    rows.append(("mpad_objective_kernel_N2048", us_kern,
                 f"pallas_on_{jax.default_backend()}"))


def bench_kernels(rows):
    from repro.kernels.knn_topk import knn_ref, knn_topk_pallas
    q = jax.random.normal(jax.random.key(0), (128, 64))
    x = jax.random.normal(jax.random.key(1), (4096, 64))
    us_k = _timeit(knn_topk_pallas, q, x, 10, reps=2)
    us_r = _timeit(knn_ref, q, x, 10)
    rows.append(("knn_topk_pallas_4096", us_k,
                 f"pallas_on_{jax.default_backend()}"))
    rows.append(("knn_ref_jnp_4096", us_r, "oracle"))


def bench_fit(rows):
    from repro.core import MPADConfig, fit_mpad
    x = jax.random.normal(jax.random.key(0), (600, 128))
    t0 = time.time()
    res = fit_mpad(x, MPADConfig(m=16, iters=48))
    jax.block_until_ready(res.matrix)
    rows.append(("mpad_fit_600x128_m16", (time.time() - t0) * 1e6,
                 f"phi_final={float(res.objective_trace[-1, -1]):.3f}"))


def bench_accuracy(rows):
    """Fig.1 subset: fasttext stand-in, ratio 0.2, k=10, all methods."""
    from benchmarks.fig1_accuracy import run
    _, summary = run(["fasttext"], [0.2], [10], iters=32)
    for (ds, name), acc in summary.items():
        rows.append((f"amk_{ds}_{name}", 0.0, f"A_m(10)={acc:.4f}"))


def bench_serving(rows):
    from repro.core import MPADConfig
    from repro.search import SearchEngine, ServeConfig, knn_search
    from repro.search.knn import recall_at_k
    key = jax.random.key(0)
    centers = jax.random.normal(key, (32, 128)) * 2
    lab = jax.random.randint(jax.random.fold_in(key, 1), (4096,), 0, 32)
    corpus = centers[lab] + 0.4 * jax.random.normal(
        jax.random.fold_in(key, 2), (4096, 128))
    queries = corpus[:256] + 0.05 * jax.random.normal(
        jax.random.fold_in(key, 3), (256, 128))
    eng_full = SearchEngine(corpus, ServeConfig(target_dim=None))
    eng_mpad = SearchEngine(corpus, ServeConfig(
        target_dim=16, rerank=64, mpad=MPADConfig(m=16, iters=32)))
    _, truth = knn_search(queries, corpus, 10)
    us_full = _timeit(eng_full.search, queries, 10, reps=3)
    us_mpad = _timeit(eng_mpad.search, queries, 10, reps=3)
    _, found = eng_mpad.search(queries, 10)
    rec = float(recall_at_k(found, truth))
    rows.append(("serve_full_dim128_4096x256q", us_full, "exact"))
    rows.append(("serve_mpad_dim16_rerank64", us_mpad,
                 f"recall@10={rec:.4f}"))


def bench_ivfpq(rows):
    """IVF-PQ recall/latency sweep (nprobe x pq_subspaces) vs the flat scan
    on a 16k x 128 clustered corpus — the acceptance grid for the residual
    index subsystem."""
    from repro.search import SearchEngine, ServeConfig, knn_search
    from repro.search.knn import recall_at_k
    key = jax.random.key(0)
    centers = jax.random.normal(key, (64, 128)) * 1.5
    lab = jax.random.randint(jax.random.fold_in(key, 1), (16384,), 0, 64)
    corpus = centers[lab] + 0.4 * jax.random.normal(
        jax.random.fold_in(key, 2), (16384, 128))
    nq = 256
    queries = corpus[:nq] + 0.05 * jax.random.normal(
        jax.random.fold_in(key, 3), (nq, 128))
    _, truth = knn_search(queries, corpus, 10)

    eng_flat = SearchEngine(corpus, ServeConfig(target_dim=None))
    us_flat = _timeit(eng_flat.search, queries, 10, reps=3)
    _, found = eng_flat.search(queries, 10)
    rec_flat = float(recall_at_k(found, truth))
    rows.append(("serve_flat_dim128_16384x256q", us_flat,
                 f"recall@10={rec_flat:.4f} us_per_q={us_flat / nq:.1f}"))

    import dataclasses
    for m in (8, 16):
        # one build per code budget; nprobe is a query-time knob
        eng = SearchEngine(corpus, ServeConfig(
            target_dim=None, rerank=64, index="ivfpq", nlist=256,
            pq_subspaces=m, pq_centroids=256))
        for nprobe in (2, 4, 8):
            eng.config = dataclasses.replace(eng.config, nprobe=nprobe)
            us = _timeit(eng.search, queries, 10, reps=3)
            _, found = eng.search(queries, 10)
            rec = float(recall_at_k(found, truth))
            rows.append((f"serve_ivfpq_m{m}_nprobe{nprobe}", us,
                         f"recall@10={rec:.4f} us_per_q={us / nq:.1f} "
                         f"speedup_vs_flat={us_flat / us:.1f}x"))


# --- one-program serving trajectory (BENCH_serve.json) -----------------------

@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def _prepr_ivfpq_search(cent, lists, cbs, codes, bias, q, k, nprobe):
    """The pre-PR-2 per-stage scan, pinned: einsum tables + scattered
    ``codes[cid]``/``bias[cid]`` gathers + per-subspace lookup loop. Kept
    verbatim so BENCH_serve.json's ``staged_vs_fused`` rows keep measuring
    against the same baseline as the repo evolves."""
    nq = q.shape[0]
    m, kc, dsub = cbs.shape
    cd2 = (jnp.sum(q * q, 1)[:, None] + jnp.sum(cent * cent, 1)[None, :]
           - 2.0 * q @ cent.T)
    _, probe = jax.lax.top_k(-cd2, nprobe)
    cd2p = jnp.take_along_axis(cd2, probe, axis=1)
    cand = lists[probe].reshape(nq, -1)
    valid = cand >= 0
    cid = jnp.maximum(cand, 0)
    qs = q.reshape(nq, m, dsub)
    tables = (jnp.sum(cbs ** 2, -1)[None]
              - 2.0 * jnp.einsum("qmd,mkd->qmk", qs, cbs))
    base = jnp.repeat(cd2p, lists.shape[1], axis=1)
    base = jnp.where(valid, base + bias[cid], jnp.inf)
    ccodes = codes[cid]
    d2 = base
    for j in range(m):
        d2 = d2 + jnp.take_along_axis(tables[:, j, :], ccodes[:, :, j],
                                      axis=1)
    neg, sel = jax.lax.top_k(-d2, k)
    ids = jnp.where(sel >= 0,
                    jnp.take_along_axis(cand, jnp.maximum(sel, 0), axis=1),
                    -1)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), ids


@functools.partial(jax.jit, static_argnames=("k",))
def _prepr_rerank(queries, corpus, cand, k):
    cv = corpus[jnp.maximum(cand, 0)]
    d2 = jnp.sum((cv - queries[:, None, :]) ** 2, axis=-1)
    d2 = jnp.where(cand >= 0, d2, jnp.inf)
    neg, sel = jax.lax.top_k(-d2, k)
    return (jnp.sqrt(jnp.maximum(-neg, 0.0)),
            jnp.take_along_axis(cand, sel, axis=1))


def bench_serve_fused(rows, json_doc=None, fast=False):
    """The serving perf trajectory: p50/p95 us per query batch, QPS and
    recall@10 per index kind x lut_dtype on the 16k x 128 grid, plus the
    one-program engine vs the pre-PR per-stage pipeline (the PR-2
    acceptance row: >= 2x QPS at recall@10 >= 0.9)."""
    import dataclasses
    from repro.search import build_engine, knn_search
    from repro.search.knn import recall_at_k
    n, dim, nq, k = 16384, 128, 256, 10
    key = jax.random.key(0)
    centers = jax.random.normal(key, (64, dim)) * 1.5
    lab = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, 64)
    corpus = centers[lab] + 0.4 * jax.random.normal(
        jax.random.fold_in(key, 2), (n, dim))
    queries = corpus[:nq] + 0.05 * jax.random.normal(
        jax.random.fold_in(key, 3), (nq, dim))
    _, truth = knn_search(queries, corpus, k)
    # staged-baseline knobs (shared with the pinned pre-PR pipeline below)
    base_cfg = dict(target_dim=None, rerank=64, nlist=256, nprobe=8,
                    pq_subspaces=16, pq_centroids=256)
    # engines are declared by pipeline-spec strings (the composable API);
    # each spec lowers onto the same knobs as the old flat configs
    grid = [("ivfpq", "ivf256x8>pq16x256", ("f32", "bf16", "int8"))]
    if not fast:
        grid = [("flat", "flat", ("f32",)),
                ("ivf", "ivf256x8", ("f32",)),
                ("pq", "pq16x256", ("f32", "bf16", "int8"))] + grid
    reps = 5 if fast else 9
    doc_rows, sweep_rows = [], []
    for index, spec, luts in grid:
        eng = build_engine(corpus, spec)
        # the bf16/int8-vs-f32 QPS ratio is a regression gate
        # (check_regression.py), so the three LUT widths are timed
        # interleaved — configs prebuilt so the timed call is search-only
        cfgs = {lut: dataclasses.replace(eng.config, lut_dtype=lut)
                for lut in luts}

        def _lut_call(lut):
            def go():
                eng.config = cfgs[lut]
                return eng.search(queries, k)
            return go

        ts_lut = _timeit_interleaved({lut: _lut_call(lut) for lut in luts},
                                     reps=max(reps, 9), calls=2)
        for lut in luts:
            eng.config = cfgs[lut]
            ts = sorted(ts_lut[lut])
            p50, p95 = _pctl(ts, 50), _pctl(ts, 95)
            _, found = eng.search(queries, k)
            rec = float(recall_at_k(found, truth))
            qps = nq / (p50 * 1e-6)
            rows.append((f"serve_fused_{index}_lut_{lut}", p50,
                         f"recall@10={rec:.4f} p95_us={p95:.0f} "
                         f"qps={qps:.0f}"))
            doc_rows.append(dict(index=index, lut_dtype=lut, batch=nq,
                                 p50_us=round(p50, 1), p95_us=round(p95, 1),
                                 us_per_query_p50=round(p50 / nq, 2),
                                 qps=round(qps), recall_at_10=round(rec, 4)))
        # batch sweep: p50 latency across the traffic range {1, 8, 64, 256}.
        # On the read-only ivfpq engine small buckets (<= compact_batch)
        # take the nprobe-proportional compact scan whenever the posting-
        # mass bound beats the padded width (bit-identical results, smaller
        # program); the opt-in re-rank pre-filter (prefilter_batch) stays
        # off here — on this corpus the PQ error bound is loose, so it
        # admits nearly all candidates and costs more than it saves.
        eng.config = dataclasses.replace(eng.config, lut_dtype=luts[0])
        for b in (1, 8, 64, 256):
            ts_b = _timeit_dist(eng.search, queries[:b], k, reps=reps)
            p50_b = _pctl(ts_b, 50)
            compact = (index == "ivfpq" and eng.last_bucket is not None
                       and eng.last_bucket <= eng.config.compact_batch
                       and eng._scan_cap(eng.config.nprobe) > 0)
            rows.append((f"serve_sweep_{index}_b{b}", p50_b,
                         f"us_per_q={p50_b / b:.1f} "
                         f"qps={b / (p50_b * 1e-6):.0f} "
                         f"compact={'Y' if compact else 'n'}"))
            sweep_rows.append(dict(
                index=index, lut_dtype=luts[0], batch=b,
                p50_us=round(p50_b, 1),
                us_per_query_p50=round(p50_b / b, 2),
                qps=round(b / (p50_b * 1e-6)),
                compact_scan=compact))
        if index == "ivfpq":
            if json_doc is not None:
                # scan-path metadata: what the compact scan + narrow codes
                # buy per query (roofline.py turns these into bytes moved)
                idxp = eng.state.index.payload
                json_doc["scan"] = dict(
                    index="ivfpq",
                    code_dtype=str(idxp.codes.dtype),
                    code_bytes_per_vector=(
                        idxp.codes.dtype.itemsize * idxp.codes.shape[1]),
                    nprobe=eng.config.nprobe,
                    max_cell=int(idxp.lists.shape[1]),
                    padded_scan_width=(eng.config.nprobe
                                       * int(idxp.lists.shape[1])),
                    compact_scan_cap=eng._scan_cap(eng.config.nprobe),
                    compact_batch=eng.config.compact_batch,
                    prefilter_batch=eng.config.prefilter_batch)
            # staged baseline: pre-PR pipeline = separate scan + re-rank
            # programs over the same index arrays
            idx = eng.state.index.payload        # the dense IVFPQIndex
            eng.config = dataclasses.replace(eng.config, lut_dtype="f32")

            def staged(q, k):
                _, cand = _prepr_ivfpq_search(
                    idx.centroids, idx.lists, idx.codebooks, idx.codes,
                    idx.bias, q, base_cfg["rerank"], base_cfg["nprobe"])
                return _prepr_rerank(q, eng.state.corpus, cand, k)

            staged_rows = []
            for b in (64, nq):
                # the b64 speedup is a regression gate: staged and fused
                # are timed back-to-back every round and the speedup is
                # the MEDIAN PER-ROUND RATIO — pairing cancels machine
                # drift that medians-of-separate-windows cannot; short
                # calls, so extra rounds are cheap insurance
                ts_sf = _timeit_interleaved(
                    {"staged": lambda: staged(queries[:b], k),
                     "fused": lambda: eng.search(queries[:b], k)},
                    reps=max(reps, 11), calls=2)
                p50_s = _pctl(sorted(ts_sf["staged"]), 50)
                p50_f = _pctl(sorted(ts_sf["fused"]), 50)
                _, f_s = staged(queries[:b], k)
                _, f_f = eng.search(queries[:b], k)
                rec_s = float(recall_at_k(f_s, truth[:b]))
                rec_f = float(recall_at_k(f_f, truth[:b]))
                speedup = _pctl(sorted(s / f for s, f in
                                       zip(ts_sf["staged"],
                                           ts_sf["fused"])), 50)
                rows.append((f"serve_staged_vs_fused_ivfpq_b{b}", p50_f,
                             f"staged_us={p50_s:.0f} speedup={speedup:.2f}x "
                             f"recall_fused={rec_f:.4f}"))
                staged_rows.append(dict(
                    index="ivfpq", batch=b, staged_p50_us=round(p50_s, 1),
                    fused_p50_us=round(p50_f, 1),
                    speedup=round(speedup, 2),
                    staged_recall_at_10=round(rec_s, 4),
                    fused_recall_at_10=round(rec_f, 4)))
            if json_doc is not None:
                json_doc["staged_vs_fused"] = staged_rows

            # --- observability overhead + per-stage breakdown ---------
            # the overhead numbers are regression gates (<=1% with a
            # tracer attached but inert, <=3% with histograms recording)
            # so the three postures run interleaved on the SAME engine
            # and the overhead is the median per-round base/variant time
            # ratio — pairing cancels machine drift
            from repro.search import TraceConfig, deep_trace
            from repro.search.tracing import Tracer

            def _posture(tracer):
                def go():
                    eng._tracer = tracer
                    return eng.search(queries, k)
                return go

            # the gated overheads are ~1%, far under this box class's
            # round-to-round noise, so the estimator needs many paired
            # rounds with short bursts to converge (25x3 per posture)
            ts_o = _timeit_interleaved(
                {"base": _posture(None),
                 "traced_off": _posture(Tracer(TraceConfig(
                     histograms=False))),
                 "hist_on": _posture(Tracer(TraceConfig()))},
                reps=max(reps, 25), calls=3)
            eng._tracer = None
            p50_o = {name: _pctl(sorted(ts), 50)
                     for name, ts in ts_o.items()}

            # upper-quartile paired ratio, not the median: the true
            # costs (an attribute check; a bisect + two adds) sit far
            # below this box class's noise floor, and load noise is
            # one-sided (spikes only slow calls down) — a REAL hot-path
            # regression (a stray sync/copy is >=1ms on this batch)
            # shifts the whole ratio distribution and still trips the
            # gate, while round-level spikes no longer do
            def _overhead(variant):
                return max(0.0, 1.0 - _pctl(sorted(
                    b / v for b, v in zip(ts_o["base"], ts_o[variant])),
                    75))

            ov_off = _overhead("traced_off")
            ov_hist = _overhead("hist_on")
            rows.append(("serve_observability_overhead", 0.0,
                         f"traced_off={ov_off:.2%} hist_on={ov_hist:.2%} "
                         f"base_p50_us={p50_o['base']:.0f}"))
            # per-stage attribution across the traffic range: the staged
            # re-run deep_trace samples in production, at bench precision
            kwd = dict(nprobe=eng.config.nprobe, rerank=eng.config.rerank,
                       backend=eng.config.pq_backend,
                       lut_dtype="f32", scan_cap=0, prefilter=0)
            breakdown = []
            for b in (1, 64, nq):
                runs = [deep_trace(eng, queries[:b], k, kwd)
                        for _ in range(3)]
                names = [s for s, _ in runs[0]["stages"]]
                med = {s: sorted(r["stages"][i][1] for r in runs)[1]
                       for i, s in enumerate(names)}
                e2e = sorted(r["e2e_ms"] for r in runs)[1]
                total = sum(med.values()) or 1.0
                shares = {s: round(ms / total, 3) for s, ms in med.items()}
                rows.append((f"serve_latency_breakdown_b{b}", e2e * 1e3,
                             " ".join(f"{s}={shares[s]:.0%}"
                                      for s in names)))
                breakdown.append(dict(
                    index="ivfpq", batch=b, e2e_ms=round(e2e, 4),
                    stages_ms={s: round(ms, 4) for s, ms in med.items()},
                    shares=shares))
            if json_doc is not None:
                json_doc["observability"] = dict(
                    index="ivfpq", batch=nq,
                    p50_us_base=round(p50_o["base"], 1),
                    p50_us_traced_off=round(p50_o["traced_off"], 1),
                    p50_us_hist_on=round(p50_o["hist_on"], 1),
                    trace_off_overhead=round(ov_off, 4),
                    hist_overhead=round(ov_hist, 4))
                json_doc["latency_breakdown"] = breakdown
    if json_doc is not None:
        json_doc["rows"] = doc_rows
        json_doc["batch_sweep"] = sweep_rows
        json_doc["config"] = dict(corpus=n, dim=dim, batch=nq, k=k,
                                  **base_cfg)


def bench_stream(rows, json_doc=None, fast=False):
    """Streaming (mutable) serving: interleaved 90/10 read/write workload
    on the 16k x 128 ivfpq grid — update throughput, search latency under
    write load, and the staleness story (fresh rows served exactly from
    the delta vs re-coded through PQ after compaction)."""
    import numpy as np

    from repro.search import (SearchEngine, ServeConfig, StreamConfig,
                              knn_search)
    from repro.search.knn import recall_at_k
    n, dim, nq, k = 16384, 128, 256, 10
    key = jax.random.key(0)
    centers = jax.random.normal(key, (64, dim)) * 1.5
    lab = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, 64)
    corpus = centers[lab] + 0.4 * jax.random.normal(
        jax.random.fold_in(key, 2), (n, dim))
    queries = corpus[:nq] + 0.05 * jax.random.normal(
        jax.random.fold_in(key, 3), (nq, dim))
    _, truth = knn_search(queries, corpus, k)
    wb = 256
    # cell_slack widens every probed cell, so it is a latency knob as much
    # as a capacity one: ~128 slots absorbs this workload's appends (~4k
    # rows over 256 cells) without inflating the probe-scan width
    eng = SearchEngine(corpus, ServeConfig(
        target_dim=None, rerank=64, index="ivfpq", nlist=256, nprobe=8,
        pq_subspaces=16, pq_centroids=256,
        stream=StreamConfig(delta_capacity=1024, write_bucket=wb,
                            row_capacity=n + 16384, cell_slack=128)))
    rng = np.random.RandomState(0)
    next_id = n

    def write_batch():
        nonlocal next_id
        ids = np.arange(next_id, next_id + wb)
        next_id += wb
        vecs = rng.randn(wb, dim).astype(np.float32)
        eng.upsert(ids, vecs)
        jax.block_until_ready(eng.store.delta_count)

    # warmup every program (search / upsert / delete / compact)
    eng.search(queries, k)
    write_batch()
    eng.delete(np.arange(n, n + 8))
    eng.compact()
    # pure write throughput
    reps_w = 3 if fast else 6
    t0 = time.perf_counter()
    for _ in range(reps_w):
        write_batch()
    ups_per_s = reps_w * wb / (time.perf_counter() - t0)
    # interleaved 90/10: 9 search batches per write batch
    rounds = 2 if fast else 4
    ts = []
    for _ in range(rounds):
        write_batch()
        for _ in range(9):
            t0 = time.perf_counter()
            out = eng.search(queries, k)
            jax.block_until_ready(out)
            ts.append((time.perf_counter() - t0) * 1e6)
    ts.sort()
    p50 = _pctl(ts, 50)
    _, found = eng.search(queries, k)
    rec = float(recall_at_k(found, truth))
    qps = nq / (p50 * 1e-6)
    # staleness: fresh rows served exactly from the delta, then re-coded
    # through the residual PQ by compaction
    fresh = queries[:128] + 0.001 * rng.randn(128, dim).astype(np.float32)
    fresh_ids = np.arange(next_id, next_id + 128)
    eng.upsert(fresh_ids, fresh)
    _, f1 = eng.search(queries[:128], 1)
    rec_delta = float((np.asarray(f1)[:, 0] == fresh_ids).mean())
    eng.compact()
    _, f2 = eng.search(queries[:128], 1)
    rec_compacted = float((np.asarray(f2)[:, 0] == fresh_ids).mean())
    rows.append(("stream_ivfpq_90_10", p50,
                 f"ups_per_s={ups_per_s:.0f} qps={qps:.0f} "
                 f"recall@10={rec:.4f} fresh_delta={rec_delta:.3f} "
                 f"fresh_compacted={rec_compacted:.3f} "
                 f"grow={eng.grow_count}"))
    if json_doc is not None:
        json_doc["stream"] = [dict(
            scenario="stream_90_10", index="ivfpq", write_batch=wb,
            upserts_per_sec=round(ups_per_s),
            search_p50_us=round(p50, 1), search_qps=round(qps),
            recall_at_10=round(rec, 4),
            fresh_top1_delta=round(rec_delta, 4),
            fresh_top1_compacted=round(rec_compacted, 4))]


def bench_zoo(rows, json_doc=None, fast=False):
    """Reducer & index zoo: recall@10 + QPS per registered reducer x index
    spec on one clustered grid (the ``zoo`` section of BENCH_serve.json).

    Two within-file pairs are regression gates (check_regression.py):
    OPQ's learned rotation must not lose recall vs plain PQ at equal code
    bytes (the OPQ fit's candidate set includes the un-rotated solution,
    so its reconstruction MSE is <= plain PQ by construction), and the
    MPAD reducer must hold recall vs PCA at equal output dim (the paper's
    claim, Fig.1)."""
    from repro.search import build_engine, knn_search, parse_spec
    from repro.search.knn import recall_at_k
    n, dim, nq, k = 8192, 128, 256, 10
    key = jax.random.key(0)

    # reducer grid: cluster structure in the first 96 dims plus 32
    # high-variance nuisance dims that carry no neighbor information —
    # the regime the quantile-preserving projection targets (PCA's
    # top-variance directions are exactly the nuisance dims)
    sig = dim - 32
    centers = jax.random.normal(key, (64, sig)) * 1.5
    lab = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, 64)
    signal = centers[lab] + 0.4 * jax.random.normal(
        jax.random.fold_in(key, 2), (n, sig))
    red_corpus = jnp.concatenate(
        [signal, 3.0 * jax.random.normal(jax.random.fold_in(key, 4),
                                         (n, 32))], axis=1)
    # code grid: anisotropic (decaying per-dim scales), so PQ's fixed
    # subspace split is variance-imbalanced and the learned rotation has
    # something to rebalance
    kc = jax.random.key(5)
    scales = 1.0 / jnp.sqrt(1.0 + jnp.arange(dim, dtype=jnp.float32))
    ccent = jax.random.normal(kc, (64, dim)) * 1.5
    clab = jax.random.randint(jax.random.fold_in(kc, 1), (n,), 0, 64)
    code_corpus = (ccent[clab] + 0.4 * jax.random.normal(
        jax.random.fold_in(kc, 2), (n, dim))) * scales

    grids = {}
    for gname, corpus, qkey, qscale in (
            ("reducer", red_corpus, jax.random.fold_in(key, 3), 1.0),
            ("code", code_corpus, jax.random.fold_in(kc, 3), scales)):
        queries = corpus[:nq] + 0.05 * jax.random.normal(
            qkey, (nq, dim)) * qscale
        _, truth = knn_search(queries, corpus, k)
        grids[gname] = (corpus, queries, truth)

    # each gate pair runs on one grid, so the within-file compare is
    # apples-to-apples: equal-dim reducers on the exact-scan pipeline,
    # equal-byte codes without a reducer
    specs = [("reducer", "qpad32>flat"), ("reducer", "pca32>flat"),
             ("reducer", "mlp32>flat"),
             ("code", "pq8x256"), ("code", "opq8x256")]
    if not fast:
        specs.append(("reducer", "qpad32>ivf64x8>pq8x256:i8"))
    reps = 5 if fast else 9
    zoo_rows = []
    for gname, spec_s in specs:
        corpus, queries, truth = grids[gname]
        sp = parse_spec(spec_s)
        eng = build_engine(corpus, spec_s, fit_sample=2048, seed=0)
        ts = _timeit_dist(eng.search, queries, k, reps=reps)
        p50 = _pctl(ts, 50)
        _, found = eng.search(queries, k)
        rec = float(recall_at_k(found, truth))
        qps = nq / (p50 * 1e-6)
        rows.append((f"zoo_{spec_s}", p50,
                     f"grid={gname} recall@10={rec:.4f} qps={qps:.0f}"))
        zoo_rows.append(dict(
            spec=spec_s, grid=gname,
            reducer=sp.reduce.kind if sp.reduce is not None else None,
            index=sp.kind,
            dim=sp.reduce.m if sp.reduce is not None else dim,
            code_bytes=(sp.code.subspaces if sp.code is not None else None),
            p50_us=round(p50, 1), qps=round(qps),
            recall_at_10=round(rec, 4)))
    if json_doc is not None:
        json_doc["zoo"] = zoo_rows


def bench_durability(rows, json_doc=None, fast=False):
    """Durability subsystem: what the WAL costs the write path, how fast
    crash recovery replays, and what background compaction buys search
    latency vs the blocking stall."""
    import shutil
    import tempfile

    import threading

    import numpy as np

    from repro.search import (DurabilityConfig, SearchEngine, ServeConfig,
                              StreamConfig, Wal, load_engine)
    from repro.search.durability.wal import RT_UPSERT, encode_upsert
    n, dim = (4096, 128) if fast else (16384, 128)
    wb = 256
    key = jax.random.key(0)
    corpus = jax.random.normal(key, (n, dim), jnp.float32)
    queries = corpus[:64] + 0.05 * jax.random.normal(
        jax.random.fold_in(key, 1), (64, dim))
    rng = np.random.RandomState(0)

    def mk(**stream_kw):
        stream_kw.setdefault("delta_capacity", 2048)
        return SearchEngine(corpus, ServeConfig(
            rerank=64, index="ivfpq", nlist=64, nprobe=8,
            pq_subspaces=16, pq_centroids=256,
            stream=StreamConfig(write_bucket=wb, row_capacity=3 * n,
                                cell_slack=256, **stream_kw)))

    reps = 3 if fast else 6
    batches = [rng.randn(wb, dim).astype(np.float32)
               for _ in range(reps + 1)]

    def writer(eng, base_id):
        # per-batch stateful write thunk; the delta (cap 2048) holds every
        # batch, so no compaction inside any timed region
        step = [0]

        def go():
            r = step[0]
            step[0] += 1
            ids = np.arange(base_id + r * wb, base_id + (r + 1) * wb)
            eng.upsert(ids, batches[r % len(batches)])
            return eng.store.delta_count

        return go

    work = tempfile.mkdtemp(prefix="qpad-bench-dur-")
    try:
        # --- WAL overhead on the write path -------------------------------
        # the overhead is a regression gate: WAL-off and WAL-on engines
        # write alternately (interleaved) so the on/off ratio is immune to
        # machine drift between the two measurement windows
        eng_on = mk().durable(os.path.join(work, "wal_on"),
                              DurabilityConfig(fsync="batch"))
        ts_w = _timeit_interleaved(
            {"off": writer(mk(), n), "on": writer(eng_on, n)},
            reps=max(reps, 6))          # 7 batches/engine: under the delta cap
        off = wb / (_pctl(sorted(ts_w["off"]), 50) * 1e-6)
        on = wb / (_pctl(sorted(ts_w["on"]), 50) * 1e-6)
        # throughput-loss fraction from the median per-round off/on time
        # ratio (paired: each round's two writes are temporally adjacent)
        overhead = max(0.0, 1.0 - _pctl(sorted(
            t_off / t_on for t_off, t_on in
            zip(ts_w["off"], ts_w["on"])), 50))
        rows.append(("durability_wal_overhead", 0.0,
                     f"ups_off={off:.0f} ups_on={on:.0f} "
                     f"overhead={overhead:.1%}"))

        # --- crash-recovery replay speed ----------------------------------
        rec_dir = os.path.join(work, "recover")
        eng = mk().durable(rec_dir, DurabilityConfig(fsync="batch"))
        r_rows = 2048 if fast else 16384
        for b in range(r_rows // wb):
            ids = np.arange(2 * n + b * wb, 2 * n + (b + 1) * wb)
            eng.upsert(ids, rng.randn(wb, dim).astype(np.float32))
        jax.block_until_ready(eng.store.delta_count)
        t0 = time.perf_counter()
        rec = load_engine(rec_dir)
        jax.block_until_ready(rec.store.delta_count)
        rec_s = time.perf_counter() - t0
        assert rec._replayed > 0
        rows.append(("durability_recovery", rec_s * 1e6,
                     f"rows={r_rows} seconds={rec_s:.2f} "
                     f"rows_per_s={r_rows / rec_s:.0f}"))

        # --- background vs blocking compaction ----------------------------
        def fill(eng):
            for b in range(5):          # 1280 rows: under the 1536 point
                ids = np.arange(4 * n + b * wb, 4 * n + (b + 1) * wb)
                eng.upsert(ids, batches[b % (reps + 1)])
            jax.block_until_ready(eng.store.delta_count)

        eng = mk()
        eng.search(queries, 10)         # warmup the read program
        fill(eng)
        t0 = time.perf_counter()
        eng.compact()
        stall_ms = (time.perf_counter() - t0) * 1e3
        base_ts = []
        for _ in range(8):
            t0 = time.perf_counter()
            jax.block_until_ready(eng.search(queries, 10))
            base_ts.append((time.perf_counter() - t0) * 1e6)
        base_ts.sort()
        eng = mk(background_compact=True)
        eng.search(queries, 10)
        fill(eng)
        eng.begin_compact()
        bg_ts = []
        while eng._compact_future is not None:
            t0 = time.perf_counter()
            jax.block_until_ready(eng.search(queries, 10))
            bg_ts.append((time.perf_counter() - t0) * 1e6)
        bg_ts.sort()
        p50_bg, p50_base = _pctl(bg_ts, 50), _pctl(base_ts, 50)
        rows.append(("durability_bg_compact_search", p50_bg,
                     f"baseline_p50={p50_base:.0f}us "
                     f"blocking_stall={stall_ms:.0f}ms "
                     f"samples={len(bg_ts)}"))

        # --- group commit: concurrent fsync=always burst ------------------
        # 8 writer threads of durable appends, grouped vs one-fsync-per-
        # record: grouping coalesces the burst into shared commits (the
        # regression gate asks >=2x). WAL-layer only — the fsync is the
        # entire cost, so engine programs would just add noise.
        gc_threads, gc_per = 8, (12 if fast else 24)
        payload = encode_upsert(np.arange(32, dtype=np.int32),
                                rng.randn(32, dim).astype(np.float32))

        def burst(wal):
            def writer():
                for _ in range(gc_per):
                    wal.append(RT_UPSERT, payload)
            ths = [threading.Thread(target=writer)
                   for _ in range(gc_threads)]
            t0 = time.perf_counter()
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            dt = time.perf_counter() - t0
            fsyncs = wal.stats()["fsyncs"]
            wal.close()
            return gc_threads * gc_per / dt, fsyncs

        aps_off, fs_off = burst(Wal(os.path.join(work, "gc_off"),
                                    DurabilityConfig(fsync="always")))
        aps_on, fs_on = burst(Wal(
            os.path.join(work, "gc_on"),
            DurabilityConfig(fsync="always", group_commit_ms=2.0)))
        gc_speedup = aps_on / aps_off
        rows.append(("durability_group_commit", 0.0,
                     f"grouped={aps_on:.0f}aps ungrouped={aps_off:.0f}aps "
                     f"speedup={gc_speedup:.2f}x fsyncs={fs_on}/{fs_off}"))

        # --- incremental vs full snapshot ---------------------------------
        # a small-delta engine (cap 512): the incremental link carries the
        # delta state only, so its bytes must not scale with base rows
        inc_dir = os.path.join(work, "inc")
        eng = mk(delta_capacity=512).durable(
            inc_dir, DurabilityConfig(fsync="batch"))
        t0 = time.perf_counter()
        full_bytes = os.path.getsize(eng.save(inc_dir))
        full_s = time.perf_counter() - t0
        d_rows = 256
        eng.upsert(np.arange(6 * n, 6 * n + d_rows),
                   rng.randn(d_rows, dim).astype(np.float32))
        jax.block_until_ready(eng.store.delta_count)
        t0 = time.perf_counter()
        inc_bytes = os.path.getsize(eng.save(inc_dir, incremental=True))
        inc_s = time.perf_counter() - t0
        inc_frac = inc_bytes / full_bytes
        rows.append(("durability_inc_snapshot", inc_s * 1e6,
                     f"base_rows={n} delta_rows={d_rows} "
                     f"bytes={inc_bytes} full_bytes={full_bytes} "
                     f"frac={inc_frac:.3f} full_s={full_s:.2f}"))
        if json_doc is not None:
            json_doc["durability"] = dict(
                upserts_per_sec_wal_off=round(off),
                upserts_per_sec_wal_on=round(on),
                wal_overhead_frac=round(overhead, 4),
                recovery_rows=r_rows,
                recovery_seconds=round(rec_s, 3),
                recovery_rows_per_sec=round(r_rows / rec_s),
                search_p50_us_during_bg_compact=round(p50_bg, 1),
                search_p50_us_baseline=round(p50_base, 1),
                blocking_compact_stall_ms=round(stall_ms, 1),
                group_commit=dict(
                    appends_per_sec_grouped=round(aps_on),
                    appends_per_sec_ungrouped=round(aps_off),
                    speedup=round(gc_speedup, 2),
                    fsyncs_grouped=fs_on, fsyncs_ungrouped=fs_off,
                    records=gc_threads * gc_per),
                incremental_snapshot=dict(
                    base_rows=n, delta_rows=d_rows,
                    full_bytes=full_bytes, incremental_bytes=inc_bytes,
                    bytes_frac=round(inc_frac, 4),
                    full_seconds=round(full_s, 3),
                    incremental_seconds=round(inc_s, 3)))
    finally:
        shutil.rmtree(work, ignore_errors=True)


def roofline_summary(rows):
    art = "benchmarks/artifacts/dryrun"
    if not os.path.isdir(art):
        rows.append(("roofline", 0.0, "no_dryrun_artifacts_run_dryrun_first"))
        return
    from benchmarks.roofline import load_cells, roofline_row
    cells = [roofline_row(r) for r in load_cells(art)]
    ok = [r for r in cells if r.get("status") == "ok"]
    if ok:
        worst = min(ok, key=lambda r: r["roofline_frac"])
        best = max(ok, key=lambda r: r["roofline_frac"])
        rows.append(("roofline_cells_ok", float(len(ok)),
                     f"of_{len(cells)}"))
        rows.append((f"roofline_worst_{worst['arch']}.{worst['shape']}",
                     0.0, f"frac={worst['roofline_frac']:.3f}"))
        rows.append((f"roofline_best_{best['arch']}.{best['shape']}",
                     0.0, f"frac={best['roofline_frac']:.3f}"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="BENCH_serve.json",
                    default=None, metavar="PATH",
                    help="also write the serving trajectory JSON "
                         "(default path: BENCH_serve.json)")
    ap.add_argument("--fast", action="store_true",
                    help="CI subset: kernels + the fused serving bench only")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    json_doc = {"schema": "qpad.bench_serve.v1",
                "created_unix": round(time.time())} if args.json else None
    benches = ((bench_kernels,) if args.fast
               else (bench_objective_backends, bench_kernels, bench_fit,
                     bench_serving, bench_ivfpq, bench_accuracy,
                     roofline_summary))
    for bench in benches:
        try:
            bench(rows)
        except Exception as e:                       # keep the harness going
            rows.append((bench.__name__, -1.0, f"ERROR:{type(e).__name__}"))
    serve_err = None
    try:
        bench_serve_fused(rows, json_doc=json_doc, fast=args.fast)
    except Exception as e:
        serve_err = e
        rows.append(("bench_serve_fused", -1.0, f"ERROR:{type(e).__name__}"))
    try:
        bench_stream(rows, json_doc=json_doc, fast=args.fast)
    except Exception as e:
        serve_err = serve_err or e
        rows.append(("bench_stream", -1.0, f"ERROR:{type(e).__name__}"))
    try:
        bench_durability(rows, json_doc=json_doc, fast=args.fast)
    except Exception as e:
        serve_err = serve_err or e
        rows.append(("bench_durability", -1.0, f"ERROR:{type(e).__name__}"))
    try:
        bench_zoo(rows, json_doc=json_doc, fast=args.fast)
    except Exception as e:
        serve_err = serve_err or e
        rows.append(("bench_zoo", -1.0, f"ERROR:{type(e).__name__}"))
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(json_doc, f, indent=2)
        print(f"\nwrote {args.json}")
        if serve_err is not None:
            # the serving trajectory is the CI regression gate: a truncated
            # BENCH_serve.json must fail the job, not upload silently
            raise SystemExit(
                f"serving benches failed ({serve_err!r}); "
                f"{args.json} is incomplete")


if __name__ == "__main__":
    main()
