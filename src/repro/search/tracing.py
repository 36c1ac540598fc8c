"""Request-level tracing: program spans, latency histograms, slow-query
capture, and online recall estimation.

The fused serving path is ONE XLA program per (kind, knobs, bucket), so a
host-side timer around dispatch + block can only see end-to-end latency.
Two kinds of instrument sit on top of that single number:

- **Program spans** (always on): ``SearchEngine`` wraps its host work in
  ``span`` (a ``jax.profiler.TraceAnnotation``) — ``qpad.search`` with
  ``.prepare`` / ``.launch`` / ``.trace`` children, ``qpad.upsert`` and
  ``qpad.delete`` with ``.launch``, ``qpad.wal.append``,
  ``qpad.compact.begin`` / ``.fold`` (on the ``qpad-compact`` thread) /
  ``.install``. They cost under a microsecond each with no profiler
  running; under ``jax_profile`` they land in the same trace as the
  device ops, on its clock, so a span lines up with the program it
  launched.
- **The ``Tracer``**, attached by ``engine.tracing(...)``, all opt-in:
  - *latency histograms* (``TraceConfig(histograms=True)``): every search
    records its blocked end-to-end wall time into a fixed-boundary
    log-spaced histogram (``LatencyHistogram``); ``engine.metrics()``
    derives p50/p95/p99 under ``latency.search.*`` and the Prometheus
    endpoint renders a real ``histogram`` series;
  - *slow-query log* (``slow_query_ms=T``): a ring buffer of the worst
    offenders — spec, batch shape, bucket, knob fan-out;
  - *shadow recall* (``recall_every=N``): 1-in-N queries are re-answered
    exactly (brute force against the live store — tombstone-aware on
    streaming engines) and the observed recall@k feeds a
    ``recall.estimate_at_k`` EMA gauge plus, when a maintenance policy is
    configured, ``MaintenancePolicy.observe_recall``.

With every ``Tracer`` feature off ``Tracer.active`` is False and the
serve path skips even the timestamp.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import threading
import time
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .knn import knn_search, recall_at_k
from .metrics import HistogramSnapshot, LatencyMetrics, RecallMetrics

__all__ = ["TraceConfig", "Tracer", "LatencyHistogram", "jax_profile",
           "span"]


def span(name: str):
    """A host span ``name`` for ``with``: a ``jax.profiler.TraceAnnotation``,
    recorded on the profiler's clock (beside the device ops) while a
    profiler session runs, and next to free otherwise."""
    return jax.profiler.TraceAnnotation(name)


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
    slow_query_ms: Optional[float] = None   # ring-buffer capture threshold
    slow_query_capacity: int = 64
    recall_every: int = 0            # 1-in-N shadow-exact checks (0 = off)
    recall_alpha: float = 0.1        # EMA coefficient for the recall gauge

    def __post_init__(self):
        if self.recall_every < 0:
            raise ValueError("recall_every must be >= 0")
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
    """Per-engine trace state: histograms, slow-query ring, recall EMA.
    Attached by ``SearchEngine.tracing()``; the serve path calls
    ``on_search`` after dispatch. Thread-safe against concurrent
    ``MetricsServer`` scrapes (one lock around all mutation and
    snapshotting)."""

    def __init__(self, config: TraceConfig = TraceConfig()):
        self.config = config
        self._lock = threading.Lock()
        self._e2e = LatencyHistogram()
        self._slow: list = []            # ring buffer of slow-query dicts
        self.queries = 0                 # search calls seen
        self.slow_queries = 0            # total over-threshold (>= ring)
        self.recall_ema: Optional[float] = None
        self.recall_last: Optional[float] = None
        self.recall_k: Optional[int] = None
        self.recall_samples = 0

    @property
    def active(self) -> bool:
        c = self.config
        return bool(c.histograms or c.slow_query_ms is not None
                    or c.recall_every)

    # -- recording ----------------------------------------------------------

    def on_search(self, engine, queries, nq: int, k: int, kw: Mapping,
                  t0: float, d, ids):
        """Finish one traced search: block, time, and run whichever
        instruments sampled this call. ``queries`` is the padded bucket
        batch; ``t0`` the host timestamp the engine took before dispatch;
        ``d``/``ids`` the (lazy) full-bucket result."""
        c = self.config
        jax.block_until_ready((d, ids))
        e2e_ms = (time.perf_counter() - t0) * 1e3
        with self._lock:
            n = self.queries
            self.queries += 1
        shadow = None
        if c.recall_every and n % c.recall_every == 0:
            shadow = shadow_recall(engine, queries, nq, k, ids)
        self._commit(engine, nq, k, kw, e2e_ms, shadow)

    def _commit(self, engine, nq, k, kw, e2e_ms, shadow):
        c = self.config
        with self._lock:
            if c.histograms:
                self._e2e.record(e2e_ms)
            if shadow is not None:
                r, kk = shadow
                a = c.recall_alpha
                self.recall_ema = (r if self.recall_ema is None
                                   else a * r + (1.0 - a) * self.recall_ema)
                self.recall_last, self.recall_k = r, kk
                self.recall_samples += 1
            if c.slow_query_ms is not None and e2e_ms >= c.slow_query_ms:
                self.slow_queries += 1
                self._slow.append({
                    "e2e_ms": e2e_ms, "batch": nq,
                    "bucket": engine.last_bucket, "k": k,
                    "spec": self._spec(engine),
                    "nprobe": kw.get("nprobe"),
                    "rerank": kw.get("rerank"),
                    "lut_dtype": kw.get("lut_dtype"),
                    "scan_cap": kw.get("scan_cap"),
                    "prefilter": kw.get("prefilter"),
                    "seq": self.queries - 1})
                if len(self._slow) > c.slow_query_capacity:
                    del self._slow[0]
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
                queries=self.queries,
                slow_queries=self.slow_queries,
                slow_query_ms=self.config.slow_query_ms)
            recall = RecallMetrics(
                estimate_at_k=self.recall_ema, k=self.recall_k,
                samples=self.recall_samples, last=self.recall_last)
        return latency, recall

    def slow_query_log(self) -> list:
        """The current ring-buffer contents, oldest first (copies)."""
        with self._lock:
            return [dict(e) for e in self._slow]


@contextlib.contextmanager
def jax_profile(logdir: str):
    """Profile the enclosed block via ``jax.profiler`` into ``logdir``
    (an ``.xplane.pb`` for TensorBoard/Perfetto/xprof): the device ops
    and the engine's program spans (``span``) on one clock."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # the spans name the host work; the
    #                                  Python tracer would log every call
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
