"""Request-level tracing: program spans, latency histograms, slow-query
capture, shadow-exact recall.

The contracts pinned here:

* **program spans** — a ``jax.profiler`` trace holds ``qpad.search`` with
  its ``.prepare`` / ``.launch`` (/ ``.trace``) children nested on the
  calling thread, the write path's ``qpad.upsert`` / ``qpad.delete`` with
  ``.launch`` and ``qpad.wal.append``, and a background compaction's
  ``qpad.compact.fold`` on the worker's own host line.
* **zero interference** — tracing and profiling change no results and
  never move the engine's pinned ``compile_count``.
* **honest instruments** — histogram percentiles interpolate within the
  winning log-spaced bucket; the slow-query ring trims to capacity but
  keeps counting; shadow recall scores against the LIVE rows
  (tombstone-aware on streaming engines).
"""
import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.search import (DurabilityConfig, SearchEngine, ServeConfig,
                          StreamConfig, TraceConfig, build_engine,
                          jax_profile)
from repro.search.tracing import LatencyHistogram, shadow_recall, span

pytestmark = pytest.mark.durability

N, DIM, K = 600, 32, 10


def _data(seed=0, n=N, d=DIM):
    key = jax.random.key(seed)
    centers = jax.random.normal(key, (12, d)) * 2
    lab = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, 12)
    return centers[lab] + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 2), (n, d))


def _queries(n=8, seed=3):
    return jnp.asarray(np.asarray(_data(seed=seed, n=n), np.float32))


def _host_events(tmp_path, fn):
    """Run ``fn`` under ``jax_profile`` inside a ``test.caller`` span;
    returns the trace's ``qpad.*`` host events as (line, name, start, end)
    in start order, and the line the caller ran on."""
    with jax_profile(str(tmp_path)):
        with span("test.caller"):
            fn()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out, caller = [], None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line, ln in enumerate(plane.lines):
            for e in ln.events:
                if e.name == "test.caller":
                    caller = line
                elif e.name.startswith("qpad."):
                    out.append((line, e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return sorted(out, key=lambda e: e[2]), caller


def _children(events, parent):
    """Names of the events inside ``parent`` on its line, in order."""
    line, _, lo, hi = parent
    return [e[1] for e in events
            if e is not parent and e[0] == line and lo <= e[2]
            and e[3] <= hi]


def _one(events, name):
    (e,) = [e for e in events if e[1] == name]
    return e


@pytest.mark.parametrize("traced", [False, True])
def test_search_spans_nest_on_the_calling_thread(tmp_path, traced):
    """A read-only search: qpad.search on the caller's line, holding
    .prepare then .launch (then .trace, with a tracer attached only)."""
    eng = build_engine(_data(), "ivf12x4>pq8x64>rr40")
    if traced:
        eng.tracing()
    q = _queries()
    eng.search(q, K)                                 # compile outside
    events, caller = _host_events(tmp_path, lambda: eng.search(q, K))
    search = _one(events, "qpad.search")
    assert search[0] == caller
    want = ["qpad.search.prepare", "qpad.search.launch"]
    if traced:
        want.append("qpad.search.trace")
    assert _children(events, search) == want


def _stream_engine(**stream_kw):
    return SearchEngine(_data(), ServeConfig(
        index="flat", rerank=128,
        stream=StreamConfig(delta_capacity=64, **stream_kw)))


@pytest.mark.parametrize("op,children", [
    ("search", ["qpad.search.prepare", "qpad.search.launch"]),
    ("upsert", ["qpad.upsert.launch"]),
    ("delete", ["qpad.delete.launch"])])
def test_stream_spans_nest_on_the_calling_thread(tmp_path, op, children):
    eng = _stream_engine()
    q = _queries()
    calls = {"search": lambda: eng.search(q, K),
             "upsert": lambda: eng.upsert(
                 np.arange(600, 604, dtype=np.int32), _queries(4, 5)),
             "delete": lambda: eng.delete(np.asarray([1, 2], np.int32))}
    calls[op]()                                      # compile outside
    events, caller = _host_events(tmp_path, calls[op])
    top = _one(events, f"qpad.{op}")
    assert top[0] == caller
    assert _children(events, top) == children


def test_durable_write_logs_under_its_span(tmp_path):
    eng = _stream_engine().durable(str(tmp_path / "d"),
                                   DurabilityConfig(fsync="batch"))
    ids, rows = np.arange(600, 604, dtype=np.int32), _queries(4, 5)
    eng.upsert(ids, rows)
    events, _ = _host_events(tmp_path / "trace",
                             lambda: eng.upsert(ids + 4, rows))
    assert _children(events, _one(events, "qpad.upsert")) == [
        "qpad.wal.append", "qpad.upsert.launch"]


def test_compaction_fold_runs_on_its_own_host_line(tmp_path):
    """A background compaction: begin and install on the caller's line,
    the fold on the worker's. Both lines are named after the process on
    the CPU, so the line, not its name, tells the threads apart."""
    eng = _stream_engine(background_compact=True)
    gate = threading.Event()
    eng.crash_hook = lambda p: gate.wait(30) if p == "compact_task" else None

    def fold():
        eng.upsert(np.arange(600, 660, dtype=np.int32), _queries(60, 5))
        assert eng.metrics().compact.pending
        gate.set()
        eng.finish_compact()

    events, caller = _host_events(tmp_path, fold)
    begin = _one(events, "qpad.compact.begin")
    folded = _one(events, "qpad.compact.fold")
    install = _one(events, "qpad.compact.install")
    assert begin[0] == install[0] == caller
    assert folded[0] != caller
    assert begin[3] <= folded[2] and folded[3] <= install[3]


@pytest.mark.parametrize("spec,phases", [
    ("qpad8>ivf12x4>pq8x64>rr40", ["fit", "reduce", "coarse", "assign",
                                   "codebooks", "encode", "layout"]),
    ("ivf12x4", ["coarse", "assign", "layout"]),
    ("pq8x64>rr40", ["codebooks", "encode"])])
def test_build_spans_open_in_order(tmp_path, spec, phases):
    """Set-up: qpad.build on the caller's line, holding one span a phase
    of the index build in the order the build runs them."""
    events, caller = _host_events(tmp_path, lambda: build_engine(
        _data(), spec, fit_sample=256))
    build = _one(events, "qpad.build")
    assert build[0] == caller
    assert _children(events, build) == [f"qpad.build.{p}" for p in phases]


@pytest.mark.parametrize("spec,knobs", [
    ("ivf12x4>pq8x64>rr40", {}),
    ("ivf12x4>pq8x64>rr40", {"scan_cap": 128}),
    ("ivf12x4", {})])
def test_lowered_search_holds_the_probe_inside_the_scan(spec, knobs):
    """The coarse probe (centroid distances and their top_k) is named
    qpad.probe, nested in qpad.scan, so the scan's readers still count
    it; the padded and the compact scan alike."""
    from repro.search.serve import _SEARCH_STATICS, search_fn
    eng = build_engine(_data(), spec)
    text = jax.jit(search_fn, static_argnames=_SEARCH_STATICS).lower(
        eng.state, _queries(), K, nprobe=4, rerank=40, **knobs
    ).as_text(debug_info=True)
    assert "qpad.scan/qpad.probe/dot_general" in text
    assert "qpad.scan/qpad.probe/top_k" in text


def test_profiling_changes_no_results_or_compiles(tmp_path):
    plain = build_engine(_data(), "ivf12x4>pq8x64>rr40")
    profiled = build_engine(_data(), "ivf12x4>pq8x64>rr40")
    q = _queries()
    d0, i0 = plain.search(q, K)
    out = {}

    def run():
        out["res"] = profiled.search(q, K)
        out["again"] = profiled.search(q, K)

    _host_events(tmp_path, run)
    d2, i2 = profiled.search(q, K)                   # no profiler running
    assert plain.compile_count == profiled.compile_count == 1
    for d1, i1 in (out["res"], out["again"], (d2, i2)):
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


def test_tracing_changes_no_results_or_compiles():
    """Traced searches return bit-identical results, and the tracer's
    instruments never move the engine's pinned compile_count."""
    plain = build_engine(_data(), "ivf12x4>pq8x64>rr40")
    traced = build_engine(_data(), "ivf12x4>pq8x64>rr40").tracing(
        recall_every=1, slow_query_ms=0.0)
    q = _queries()
    d0, i0 = plain.search(q, K)
    compiles = traced.compile_count
    for _ in range(3):
        d1, i1 = traced.search(q, K)
    assert traced.compile_count == compiles + 1    # the one fused program
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-6)
    assert traced.tracer.queries == 3


def test_histogram_record_and_percentiles():
    h = LatencyHistogram()
    assert h.snapshot().percentile(50) == 0.0      # empty -> 0
    for _ in range(100):
        h.record(0.04)                             # below the first bound
    snap = h.snapshot()
    assert snap.count == 100
    assert snap.sum_ms == pytest.approx(4.0)
    assert 0.0 <= snap.percentile(50) <= 0.05
    h2 = LatencyHistogram()
    h2.record(1e9)                                 # beyond every bound
    over = h2.snapshot()
    assert over.counts[-1] == 1
    assert over.bounds_ms[-1] < over.percentile(50) <= over.bounds_ms[-1] * 2
    # interpolation: uniform mass in one bucket puts p25 below p75
    h3 = LatencyHistogram()
    for _ in range(10):
        h3.record(1.0)
    s3 = h3.snapshot()
    assert s3.percentile(25) < s3.percentile(75)


def test_traceconfig_validation():
    with pytest.raises(ValueError):
        TraceConfig(recall_every=-1)
    with pytest.raises(ValueError):
        TraceConfig(recall_alpha=0.0)
    with pytest.raises(ValueError):
        TraceConfig(slow_query_ms=-0.5)


def test_slow_query_ring_trims_but_keeps_counting():
    eng = build_engine(_data(), "flat").tracing(
        slow_query_ms=0.0, slow_query_capacity=4)
    q = _queries()
    for _ in range(7):
        eng.search(q, K)
    ring = eng.tracer.slow_query_log()
    assert len(ring) == 4                          # trimmed to capacity
    assert eng.tracer.slow_queries == 7            # counter keeps going
    assert [e["seq"] for e in ring] == [3, 4, 5, 6]   # oldest dropped
    assert ring[-1]["spec"] == "flat"
    # a threshold above any real latency captures nothing
    quiet = build_engine(_data(), "flat").tracing(slow_query_ms=1e9)
    quiet.search(q, K)
    assert quiet.tracer.slow_query_log() == []
    assert quiet.tracer.slow_queries == 0


def test_shadow_recall_is_tombstone_aware():
    """Streaming: an exact flat engine scores recall 1.0 both before and
    after deletes — the shadow truth is built from the LIVE rows, so
    tombstoned rows appear in neither the served ids nor the truth. (A
    tombstone-blind shadow would count deleted rows as truth and report
    a recall drop the serving path never had.)"""
    eng = SearchEngine(_data(), ServeConfig(
        index="flat", rerank=128,
        stream=StreamConfig(delta_capacity=64)))
    q = _queries()
    _, ids = eng.search(q, K)
    r, kk = shadow_recall(eng, q, q.shape[0], K, ids)
    assert kk == K and r == pytest.approx(1.0)
    victims = np.unique(np.asarray(ids)[:, :3].ravel()).astype(np.int32)
    eng.delete(victims)
    _, ids2 = eng.search(q, K)
    assert not np.isin(np.asarray(ids2), victims).any()
    r2, kk2 = shadow_recall(eng, q, q.shape[0], K, ids2)
    assert kk2 == K and r2 == pytest.approx(1.0)
    # read-only fallback: truth against state.corpus by row index
    ro = build_engine(_data(), "flat")
    _, ids3 = ro.search(q, K)
    r3, kk3 = shadow_recall(ro, q, q.shape[0], K, ids3)
    assert kk3 == K and r3 == pytest.approx(1.0)


def test_recall_gauge_feeds_maintenance_policy():
    """When a policy is configured, every shadow sample lands in
    MaintenancePolicy.observe_recall — same EMA the dashboards show."""
    from repro.search import PolicyConfig
    eng = SearchEngine(_data(), ServeConfig(
        index="flat", rerank=128,
        stream=StreamConfig(delta_capacity=64,
                            policy=PolicyConfig(recall_floor=0.5)))
        ).tracing(recall_every=1)
    q = _queries()
    for _ in range(3):
        eng.search(q, K)
    assert eng._policy.recall_samples == 3
    assert eng._policy.recall_ema == pytest.approx(
        eng.tracer.recall_ema)
    assert eng.metrics().recall.samples == 3


def test_inert_tracer_takes_no_timestamp():
    """An all-off config is inert: the serve path takes no timestamp."""
    idle = build_engine(_data(), "flat").tracing(histograms=False)
    assert idle.tracer.active is False
    idle.search(_queries(), K)
    assert idle.tracer.queries == 0
