"""Request-level tracing: per-query timing, deep per-stage attribution,
slow-query capture, and online recall estimation.

The fused serving path is ONE XLA program per (kind, knobs, bucket), so a
host-side timer around dispatch + block can only see end-to-end latency.
This module layers three opt-in instruments on top of that single number:

- **Latency histograms** (``TraceConfig(histograms=True)``): every search
  records its blocked end-to-end wall time into a fixed-boundary
  log-spaced histogram (``LatencyHistogram``); ``engine.metrics()`` then
  derives p50/p95/p99 under ``latency.search.*`` and the Prometheus
  endpoint renders a real ``histogram`` series. Measurably cheap — the
  ≤3% overhead is gated in ``benchmarks/check_regression.py``.
- **Sampled deep trace** (``deep_trace_every=N``): 1-in-N queries re-run
  through a *staged* pipeline — project / probe / scan / re-rank as
  separate jitted programs with a ``block_until_ready`` barrier between
  stages — for exact, non-overlapping per-stage attribution that sums to
  the staged run's own end-to-end time by construction. The stage
  programs are module-level jits (jax's global cache), so sampling never
  touches the engine's compile-count pins.
- **Slow-query log** (``slow_query_ms=T``): a ring buffer of the worst
  offenders — spec, batch shape, bucket, knob fan-out, stage timings
  when a deep trace rode the same query.
- **Shadow recall** (``recall_every=N``): 1-in-N queries are re-answered
  exactly (brute force against the live store — tombstone-aware on
  streaming engines) and the observed recall@k feeds a
  ``recall.estimate_at_k`` EMA gauge plus, when a maintenance policy is
  configured, ``MaintenancePolicy.observe_recall`` — the live signal the
  drift policy and the future spec auto-tuner act on.

Everything funnels through one ``Tracer`` attached by
``engine.tracing(...)``; with every feature off ``Tracer.active`` is
False and the serve path skips even the timestamp (the ≤1% gate).
Chrome-trace/Perfetto JSON export (``trace_dir=``) covers host-side
spans; for device-side TPU profiles use the ``jax_profile`` context
manager (``jax.profiler`` trace, viewable in Perfetto/TensorBoard).
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import functools
import json
import os
import threading
import time
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .ivf import probe_cells
from .ivfpq import ivfpq_scan_given_probe
from .knn import knn_search, recall_at_k
from .metrics import HistogramSnapshot, LatencyMetrics, RecallMetrics
from .registry import ScanParams, get_ops

__all__ = ["TraceConfig", "Tracer", "LatencyHistogram", "deep_trace",
           "jax_profile"]


# Log-spaced upper bounds in milliseconds: 0.05ms .. ~105s doubling, the
# range a single fused search on anything from CPU-interpret to TPU can
# land in. Fixed boundaries keep recording O(log n_buckets) (a bisect)
# and make snapshots mergeable across engines.
_BOUNDS_MS = tuple(0.05 * 2.0 ** i for i in range(22))


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """Knobs for one ``Tracer``. Everything defaults off except the
    histograms — ``SearchEngine.tracing()`` with no arguments gives the
    cheap always-on production posture (end-to-end histograms only)."""
    histograms: bool = True          # e2e latency histogram accumulation
    trace_dir: Optional[str] = None  # Chrome-trace JSON export directory
    slow_query_ms: Optional[float] = None   # ring-buffer capture threshold
    slow_query_capacity: int = 64
    deep_trace_every: int = 0        # 1-in-N staged re-runs (0 = off)
    recall_every: int = 0            # 1-in-N shadow-exact checks (0 = off)
    recall_alpha: float = 0.1        # EMA coefficient for the recall gauge
    max_events: int = 16384          # Chrome-trace event ring capacity

    def __post_init__(self):
        if self.deep_trace_every < 0 or self.recall_every < 0:
            raise ValueError("deep_trace_every/recall_every must be >= 0")
        if not 0.0 < self.recall_alpha <= 1.0:
            raise ValueError("recall_alpha must be in (0, 1]")
        if self.slow_query_ms is not None and self.slow_query_ms < 0:
            raise ValueError("slow_query_ms must be >= 0")


class LatencyHistogram:
    """Fixed-boundary log-spaced latency accumulator (milliseconds).

    ``record`` is a bisect + two adds — cheap enough for the per-search
    hot path; ``snapshot`` freezes to the stdlib-only
    ``metrics.HistogramSnapshot`` (bounds, per-bucket counts with a
    trailing overflow bucket, sum, count) that the metrics layer derives
    percentiles from and renders as a Prometheus histogram."""

    __slots__ = ("counts", "sum_ms", "count")

    def __init__(self):
        self.counts = [0] * (len(_BOUNDS_MS) + 1)
        self.sum_ms = 0.0
        self.count = 0

    def record(self, ms: float):
        self.counts[bisect.bisect_left(_BOUNDS_MS, ms)] += 1
        self.sum_ms += ms
        self.count += 1

    def snapshot(self) -> HistogramSnapshot:
        return HistogramSnapshot(bounds_ms=_BOUNDS_MS,
                                 counts=tuple(self.counts),
                                 sum_ms=self.sum_ms, count=self.count)


# --- staged pipeline (deep trace) --------------------------------------------
# Module-level jitted stages: jax's global jit cache keys them by (shapes,
# statics, treedef), so repeated deep traces reuse compilations and the
# engine-owned program caches (compile_count — pinned by tests) never see
# them. Each stage is blocked before the next starts, so the measured
# intervals are non-overlapping and sum to the staged run's e2e.

@jax.jit
def _project_stage(proj, queries):
    from .reducers import reduce_vectors
    queries = jnp.asarray(queries, jnp.float32)
    return reduce_vectors(proj, queries)


_probe_stage = jax.jit(probe_cells, static_argnames=("nprobe", "min_cand"))

_ivfpq_scan_stage = jax.jit(
    ivfpq_scan_given_probe,
    static_argnames=("n_cand", "backend", "lut_dtype"))


@functools.partial(jax.jit, static_argnames=("n_cand", "p"))
def _scan_stage(state, qr, n_cand, p):
    ops = get_ops(state.index.kind)
    return ops.scan(state, qr, n_cand, p)


@functools.partial(jax.jit, static_argnames=("k",))
def _rerank_stage(queries, corpus, cand, k):
    from .serve import exact_rerank
    return exact_rerank(queries, corpus, cand, k)


def _block(x):
    return jax.block_until_ready(x)


def deep_trace(engine, queries, k: int, kw: Mapping) -> Optional[dict]:
    """Run one batch through the staged pipeline, timing each stage.

    ``queries`` is the engine's already-padded bucket batch and ``kw`` the
    normalized knob dict ``SearchEngine.search`` dispatched with, so the
    decomposition describes the same shapes the fused program ran (minus
    fusion, which is the point: the fused program is one opaque XLA
    computation). ivfpq decomposes as project/probe/scan/rerank (the scan
    given the probe is ``ivfpq_scan_given_probe`` — identical math to the
    fused path); other kinds as project/scan/rerank. Only read-only
    unsharded engines qualify (``engine.state``); returns None otherwise.

    Returns ``{"stages": [(name, ms), ...], "e2e_ms": float}`` where the
    stage list is ordered, non-overlapping, and sums to ``e2e_ms`` up to
    inter-stage host dispatch (the acceptance bound: within 10%).
    """
    state = engine.state
    if (state is None or engine.store is not None
            or engine.sharded_state is not None):
        return None
    ops = get_ops(state.index.kind)
    approximate = state.proj is not None or ops.lossy
    n_cand = kw["rerank"] if approximate else k
    statics = (kw["nprobe"], kw["backend"], kw["lut_dtype"], n_cand, k)

    def _run():
        stages = []
        t0 = time.perf_counter()
        qr = _block(_project_stage(state.proj, queries))
        t1 = time.perf_counter()
        stages.append(("project", (t1 - t0) * 1e3))
        if state.index.kind == "ivfpq":
            ix = state.index.payload
            probe, cand0, cd2p = _block(_probe_stage(
                ix.centroids, ix.lists, qr,
                nprobe=kw["nprobe"], min_cand=n_cand))
            t2 = time.perf_counter()
            stages.append(("probe", (t2 - t1) * 1e3))
            _, cand = _block(_ivfpq_scan_stage(
                probe, cand0, cd2p, ix.codes_cell, ix.bias_cell,
                ix.lut_w, ix.cbnorm, ix.codebooks, qr, n_cand=n_cand,
                backend=kw["backend"], lut_dtype=kw["lut_dtype"]))
            t3 = time.perf_counter()
            stages.append(("scan", (t3 - t2) * 1e3))
        else:
            p = ScanParams(nprobe=kw["nprobe"], backend=kw["backend"],
                           lut_dtype=kw["lut_dtype"])
            _, cand = _block(_scan_stage(state, qr, n_cand=n_cand, p=p))
            t3 = time.perf_counter()
            stages.append(("scan", (t3 - t1) * 1e3))
        _block(_rerank_stage(queries, state.corpus, cand, k=k))
        t4 = time.perf_counter()
        stages.append(("rerank", (t4 - t3) * 1e3))
        return {"stages": stages, "e2e_ms": (t4 - t0) * 1e3}

    warm_key = (queries.shape, state.index.kind) + statics
    warmed = getattr(engine, "_deep_warm", None)
    if warmed is None:
        warmed = engine._deep_warm = set()
    if warm_key not in warmed:      # compile pass: never time a compile
        _run()
        warmed.add(warm_key)
    return _run()


# --- shadow-exact recall -----------------------------------------------------

def shadow_recall(engine, queries, nq: int, k: int, ids) -> Optional[tuple]:
    """Brute-force the same batch against the live store and score the
    served ids: returns (recall@k', k') or None when the engine has no
    dense store to check against (donated buffers). Streaming engines are
    checked tombstone-aware via ``_gather_live`` (base survivors + live
    delta rows, mapped to external ids); read-only engines against
    ``state.corpus`` (row index == external id). k' = min(k, live rows).
    """
    queries = queries[:nq]
    if engine.store is not None:
        vecs, ext = engine._gather_live()
        if len(ext) == 0:
            return None
        kk = min(k, len(ext))
        _, idx = knn_search(queries, jnp.asarray(vecs, jnp.float32), kk)
        truth = jnp.asarray(np.asarray(ext, np.int32))[idx]
    elif engine.state is not None:
        corpus = engine.state.corpus
        kk = min(k, corpus.shape[0])
        _, truth = knn_search(queries, corpus, kk)
    else:
        return None
    return float(recall_at_k(ids[:nq, :kk], truth)), kk


# --- the tracer --------------------------------------------------------------

class Tracer:
    """Per-engine trace state: histograms, slow-query ring, Chrome-trace
    events, recall EMA. Attached by ``SearchEngine.tracing()``; the serve
    path calls ``on_search`` after blocking the result. Thread-safe
    against concurrent ``MetricsServer`` scrapes (one lock around all
    mutation and snapshotting)."""

    def __init__(self, config: TraceConfig = TraceConfig()):
        self.config = config
        self._lock = threading.Lock()
        self._e2e = LatencyHistogram()
        self._stages: dict = {}          # stage name -> LatencyHistogram
        self._slow: list = []            # ring buffer of slow-query dicts
        self._events: list = []          # Chrome-trace events (capped)
        self._origin = time.perf_counter()
        self.queries = 0                 # search calls seen
        self.slow_queries = 0            # total over-threshold (>= ring)
        self.deep_traces = 0
        self.recall_ema: Optional[float] = None
        self.recall_last: Optional[float] = None
        self.recall_k: Optional[int] = None
        self.recall_samples = 0

    @property
    def active(self) -> bool:
        c = self.config
        return bool(c.histograms or c.trace_dir is not None
                    or c.slow_query_ms is not None
                    or c.deep_trace_every or c.recall_every)

    # -- recording ----------------------------------------------------------

    def on_search(self, engine, queries, nq: int, k: int, kw: Mapping,
                  t0: float, d, ids):
        """Finish one traced search: block, time, and run whichever
        instruments sampled this call. ``queries`` is the padded bucket
        batch; ``t0`` the host timestamp the engine took before dispatch;
        ``d``/``ids`` the (lazy) full-bucket result."""
        c = self.config
        _block((d, ids))
        t1 = time.perf_counter()
        e2e_ms = (t1 - t0) * 1e3
        with self._lock:
            n = self.queries
            self.queries += 1
        trace = (c.deep_trace_every
                 and n % c.deep_trace_every == 0) or None
        if trace:
            trace = deep_trace(engine, queries, k, kw)
        shadow = None
        if c.recall_every and n % c.recall_every == 0:
            shadow = shadow_recall(engine, queries, nq, k, ids)
        self._commit(engine, nq, k, kw, t0, e2e_ms, trace, shadow)

    def _commit(self, engine, nq, k, kw, t0, e2e_ms, trace, shadow):
        c = self.config
        with self._lock:
            if c.histograms:
                self._e2e.record(e2e_ms)
                if trace:
                    for name, ms in trace["stages"]:
                        h = self._stages.get(name)
                        if h is None:
                            h = self._stages[name] = LatencyHistogram()
                        h.record(ms)
            if trace:
                self.deep_traces += 1
            if shadow is not None:
                r, kk = shadow
                a = c.recall_alpha
                self.recall_ema = (r if self.recall_ema is None
                                   else a * r + (1.0 - a) * self.recall_ema)
                self.recall_last, self.recall_k = r, kk
                self.recall_samples += 1
            slow = (c.slow_query_ms is not None
                    and e2e_ms >= c.slow_query_ms)
            if slow:
                self.slow_queries += 1
                entry = {"e2e_ms": e2e_ms, "batch": nq,
                         "bucket": engine.last_bucket, "k": k,
                         "spec": self._spec(engine),
                         "nprobe": kw.get("nprobe"),
                         "rerank": kw.get("rerank"),
                         "lut_dtype": kw.get("lut_dtype"),
                         "scan_cap": kw.get("scan_cap"),
                         "prefilter": kw.get("prefilter"),
                         "seq": self.queries - 1}
                if trace:
                    entry["stages"] = {s: ms for s, ms in trace["stages"]}
                self._slow.append(entry)
                if len(self._slow) > c.slow_query_capacity:
                    del self._slow[0]
            if c.trace_dir is not None and len(self._events) < c.max_events:
                ts_us = (t0 - self._origin) * 1e6
                self._events.append({
                    "name": "search", "ph": "X", "ts": ts_us,
                    "dur": e2e_ms * 1e3, "pid": os.getpid(), "tid": 1,
                    "args": {"batch": nq, "k": k,
                             "nprobe": kw.get("nprobe"),
                             "spec": self._spec(engine)}})
                if trace:
                    cursor = ts_us
                    for name, ms in trace["stages"]:
                        self._events.append({
                            "name": f"deep.{name}", "ph": "X",
                            "ts": cursor, "dur": ms * 1e3,
                            "pid": os.getpid(), "tid": 2, "args": {}})
                        cursor += ms * 1e3
        if shadow is not None and engine._policy is not None:
            engine._policy.observe_recall(*shadow)

    @staticmethod
    def _spec(engine) -> str:
        from .spec import format_spec
        return format_spec(engine.spec)

    # -- export -------------------------------------------------------------

    def metrics_sections(self):
        """(LatencyMetrics, RecallMetrics) for ``collect_metrics`` — the
        ``latency.*`` / ``recall.*`` dotted sections."""
        with self._lock:
            latency = LatencyMetrics(
                search=self._e2e.snapshot(),
                stages={s: h.snapshot()
                        for s, h in sorted(self._stages.items())},
                queries=self.queries,
                slow_queries=self.slow_queries,
                slow_query_ms=self.config.slow_query_ms,
                deep_traces=self.deep_traces)
            recall = RecallMetrics(
                estimate_at_k=self.recall_ema, k=self.recall_k,
                samples=self.recall_samples, last=self.recall_last)
        return latency, recall

    def slow_query_log(self) -> list:
        """The current ring-buffer contents, oldest first (copies)."""
        with self._lock:
            return [dict(e) for e in self._slow]

    def flush(self, path: Optional[str] = None) -> Optional[str]:
        """Write the buffered events as Chrome-trace JSON (open in
        ``chrome://tracing`` or Perfetto). Default path is
        ``<trace_dir>/qpad_trace_<pid>.json``; returns the path, or None
        when event capture is off. The buffer is drained."""
        with self._lock:
            if path is None:
                if self.config.trace_dir is None:
                    return None
                os.makedirs(self.config.trace_dir, exist_ok=True)
                path = os.path.join(self.config.trace_dir,
                                    f"qpad_trace_{os.getpid()}.json")
            events, self._events = self._events, []
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


@contextlib.contextmanager
def jax_profile(logdir: str):
    """Device-side profile of the enclosed block via ``jax.profiler``
    (TensorBoard/Perfetto format — the TPU-grade complement to the
    host-side Chrome trace; on TPU this captures real per-kernel device
    timelines where host timers only see dispatch+block)."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
