"""Request-level tracing: staged deep traces, latency histograms,
Chrome-trace export, slow-query capture, shadow-exact recall.

The contracts pinned here:

* **exact decomposition** — the sampled deep trace re-runs a query batch
  through staged jitted programs with a block between stages, so the
  per-stage intervals are ordered, non-overlapping, and sum to the
  staged run's own end-to-end time (the acceptance bound: within 10%).
  ivfpq decomposes as project/probe/scan/rerank, other kinds as
  project/scan/rerank; the staged scan is the same math as the fused
  program (``ivfpq_scan_given_probe``).
* **zero interference** — tracing changes no results, and deep-trace
  stage programs live in jax's global jit cache: the engine's pinned
  ``compile_count`` never moves.
* **honest instruments** — histogram percentiles interpolate within the
  winning log-spaced bucket; the slow-query ring trims to capacity but
  keeps counting; Chrome-trace export is parseable JSON whose deep
  events tile the staged span; shadow recall scores against the LIVE
  rows (tombstone-aware on streaming engines).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.search import SearchEngine, ServeConfig, StreamConfig, TraceConfig
from repro.search import build_engine, deep_trace
from repro.search.tracing import LatencyHistogram, shadow_recall

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


def _kw(eng):
    """The normalized knob dict ``search`` dispatches with."""
    cfg = eng.config
    probed = cfg.index in ("ivf", "ivfpq")
    coded = cfg.index in ("pq", "ivfpq")
    return dict(nprobe=cfg.nprobe if probed else 0, rerank=cfg.rerank,
                backend=cfg.pq_backend if coded else "jnp",
                lut_dtype=cfg.lut_dtype if coded else "f32",
                scan_cap=0, prefilter=0)


def test_deep_trace_ivfpq_decomposition():
    """The acceptance property: four named non-overlapping stages whose
    sum is within 10% of the staged run's measured end-to-end time."""
    eng = build_engine(_data(), "ivf12x4>pq8x64>rr40")
    q = _queries()
    eng.search(q, K)                     # warm the fused program
    out = deep_trace(eng, q, K, _kw(eng))
    assert out is not None
    names = [s for s, _ in out["stages"]]
    assert names == ["project", "probe", "scan", "rerank"]
    assert all(ms >= 0.0 for _, ms in out["stages"])
    total = sum(ms for _, ms in out["stages"])
    assert out["e2e_ms"] > 0.0
    assert abs(total - out["e2e_ms"]) <= 0.10 * out["e2e_ms"]


def test_deep_trace_generic_kind_and_guards():
    """Non-ivfpq kinds decompose as project/scan/rerank; engines without
    a read-only unsharded state (streaming) refuse instead of lying."""
    eng = build_engine(_data(), "ivf12x4")
    out = deep_trace(eng, _queries(), K, _kw(eng))
    assert [s for s, _ in out["stages"]] == ["project", "scan", "rerank"]
    total = sum(ms for _, ms in out["stages"])
    assert abs(total - out["e2e_ms"]) <= 0.10 * out["e2e_ms"]
    streaming = SearchEngine(_data(), ServeConfig(
        index="flat", stream=StreamConfig(delta_capacity=64)))
    assert deep_trace(streaming, _queries(), K, _kw(streaming)) is None


def test_tracing_changes_no_results_or_compiles():
    """Traced searches return bit-identical results, and the sampled
    deep traces never move the engine's pinned compile_count (the stage
    programs live in jax's global cache, not the engine's)."""
    plain = build_engine(_data(), "ivf12x4>pq8x64>rr40")
    traced = build_engine(_data(), "ivf12x4>pq8x64>rr40").tracing(
        deep_trace_every=1, recall_every=1, slow_query_ms=0.0)
    q = _queries()
    d0, i0 = plain.search(q, K)
    compiles = traced.compile_count
    for _ in range(3):
        d1, i1 = traced.search(q, K)
    assert traced.compile_count == compiles + 1    # the one fused program
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-6)
    assert traced.tracer.deep_traces == 3


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
        TraceConfig(deep_trace_every=-1)
    with pytest.raises(ValueError):
        TraceConfig(recall_alpha=0.0)
    with pytest.raises(ValueError):
        TraceConfig(slow_query_ms=-0.5)


def test_chrome_trace_export(tmp_path):
    """Events export as parseable Chrome-trace JSON; the deep-trace
    stage events tile their search's span back-to-back; flush drains."""
    eng = build_engine(_data(), "ivf12x4>pq8x64>rr40").tracing(
        trace_dir=str(tmp_path / "traces"), deep_trace_every=1)
    q = _queries()
    for _ in range(3):
        eng.search(q, K)
    path = eng.flush_trace()
    assert path is not None
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    searches = [e for e in events if e["name"] == "search"]
    deep = [e for e in events if e["name"].startswith("deep.")]
    assert len(searches) == 3 and len(deep) == 3 * 4
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0.0
    assert searches[0]["args"]["batch"] == 8
    stage_runs = [deep[i:i + 4] for i in range(0, len(deep), 4)]
    for run in stage_runs:                         # sequential tiling
        for a, b in zip(run, run[1:]):
            assert b["ts"] == pytest.approx(a["ts"] + a["dur"], abs=1e-6)
    # the buffer drained: a second flush writes an empty event list
    with open(eng.flush_trace()) as f:
        assert json.load(f)["traceEvents"] == []


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


def test_trace_dir_property_attaches_and_updates(tmp_path):
    eng = build_engine(_data(), "flat")
    assert eng.trace_dir is None and eng.flush_trace() is None
    eng.trace_dir = str(tmp_path / "t")
    assert eng.tracer is not None and eng.tracer.active
    eng.search(_queries(), K)
    path = eng.flush_trace()
    with open(path) as f:
        assert len(json.load(f)["traceEvents"]) == 1
    # an all-off config is inert: the serve path takes no timestamp
    idle = build_engine(_data(), "flat").tracing(histograms=False)
    assert idle.tracer.active is False
    idle.search(_queries(), K)
    assert idle.tracer.queries == 0
