"""Typed metrics / observability surface.

The contracts pinned here:

* **stable dotted names** — ``EngineMetrics.flatten()`` exposes the
  documented names (``wal.records``, ``wal.fsyncs``, ``stream.fill``,
  ``compact.pending``, ``policy.drift_ema``,
  ``replication.follower_lag_seq``, ``latency.search.p50``,
  ``recall.estimate_at_k``, ...); sections that do not apply drop out
  instead of renaming.
* **stats() is gone** — the PR-8 ``DeprecationWarning`` dict view
  completed its cycle; ``metrics()`` is the only counters window.
* **renderings** — ``render_prometheus`` emits ``qpad_``-prefixed
  samples with counter/gauge/histogram TYPE lines, sanitized metric
  names, escaped label values, and an ``qpad_engine_info`` label set;
  ``MetricsServer`` serves both forms over HTTP from a background
  thread (the launcher's ``--metrics-port``) and stays correct under
  concurrent scrapes mid-traffic.
* **exposition hygiene** — a pure-python lint accepts the ``/metrics``
  text of every index kind: well-formed sample lines, TYPE-before-
  sample ordering, cumulative histogram buckets ending in ``+Inf``
  whose count equals ``_count``.
"""
import json
import re
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from repro.search import (DurabilityConfig, MetricsServer, PolicyConfig,
                          SearchEngine, ServeConfig, StreamConfig,
                          build_engine, render_prometheus, seed_follower)
from repro.search.metrics import _escape_label, _sanitize_name

pytestmark = pytest.mark.durability

N, DIM, K = 600, 32, 10


def _data(seed=0, n=N, d=DIM):
    key = jax.random.key(seed)
    centers = jax.random.normal(key, (12, d)) * 2
    lab = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, 12)
    return centers[lab] + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 2), (n, d))


def _stream_cfg(**stream_kw):
    stream_kw.setdefault("delta_capacity", 64)
    return ServeConfig(index="flat", rerank=128, fit_sample=512,
                       stream=StreamConfig(**stream_kw))


def _rows(seed, n):
    return np.asarray(_data(seed=seed, n=n), np.float32)


def test_typed_surface_dotted_names():
    """The documented dotted names are present with live values; the
    sections that do not apply are None and absent from flatten()."""
    eng = SearchEngine(_data(), _stream_cfg())
    eng.upsert(np.arange(600, 620, dtype=np.int32), _rows(1, 20))
    m = eng.metrics()
    flat = m.flatten()
    assert flat["engine.index"] == "flat"
    assert flat["engine.streaming"] is True
    assert flat["engine.role"] == "primary"
    assert flat["engine.compile_count"] == eng.compile_count
    assert flat["stream.delta_used"] == 20
    assert flat["stream.fill"] == pytest.approx(20 / 64)
    assert flat["compact.pending"] is False
    assert m.wal is None and m.replication is None
    assert m.latency is None and m.recall is None  # no tracer attached
    assert not any(k.startswith(("wal.", "replication.", "latency.",
                                 "recall.")) for k in flat)
    # read-only engines have no stream/compact/snapshot sections at all
    ro = SearchEngine(_data(), ServeConfig(index="flat")).metrics()
    assert ro.stream is None and ro.compact is None and ro.snapshot is None
    assert ro.engine.streaming is False


@pytest.mark.parametrize("tile,block_rows,blocks", [(None, 600, 1),
                                                     (4 * 12 * 64, 64, 10)])
def test_build_section(monkeypatch, tile, block_rows, blocks):
    """build.* says how the index was built: its rows, the rows of one
    block of the coarse k-means and the blocks a pass takes, and the
    cells' fill with the padded scan's width at the engine's nprobe."""
    from repro.search import ivf
    if tile is not None:
        monkeypatch.setattr(ivf, "BUILD_TILE_BYTES", tile)
    eng = build_engine(_data(), "ivf12x4>pq8x64>rr40")
    flat = eng.metrics().flatten()
    max_cell = int(eng.state.index.payload.lists.shape[1])
    sizes = np.asarray((eng.state.index.payload.lists >= 0).sum(axis=1))
    assert max_cell == sizes.max()
    assert flat["build.rows"] == N
    assert flat["build.block_rows"] == block_rows
    assert flat["build.blocks"] == blocks
    assert flat["build.nlist"] == 12
    assert flat["build.max_cell"] == max_cell
    assert flat["build.mean_cell"] == pytest.approx(N / 12)
    assert flat["build.padded_width"] == 4 * max_cell
    # a flat index has no blocked pass and no cells
    ro = build_engine(_data(), "flat").metrics().flatten()
    assert ro["build.rows"] == N and ro["build.block_rows"] is None
    assert ro["build.padded_width"] is None


def test_typed_surface_wal_policy_and_follower_sections(tmp_path):
    """Durable engines expose wal.* (fsyncs, floor), policy engines
    policy.* (drift + decision counters), followers replication.*."""
    live = str(tmp_path / "live")
    eng = SearchEngine(_data(), _stream_cfg(
        policy=PolicyConfig())).durable(
        live, DurabilityConfig(fsync="batch"))
    eng.upsert(np.arange(600, 620, dtype=np.int32), _rows(1, 20))
    flat = eng.metrics().flatten()
    assert flat["wal.records"] >= 2            # snapshot mark + upsert
    assert flat["wal.fsyncs"] >= 1
    assert flat["wal.durable_seq"] <= flat["wal.last_seq"]
    assert flat["wal.floor_seq"] == 0          # pinned by the base snapshot
    assert flat["wal.fsync"] == "batch"
    assert flat["policy.observed_rows"] == 0
    assert "policy.drift_ema" in flat
    assert flat["snapshot.full"] == 1
    eng._wal.sync()
    fol = seed_follower(live)
    ff = fol.metrics().flatten()
    assert ff["engine.role"] == "follower"
    assert ff["replication.follower_lag_seq"] >= 0
    assert "wal.records" not in ff             # followers own no log


def test_stats_removed():
    """The deprecation cycle is closed: the dict view is gone and the
    typed surface is the only counters window."""
    eng = SearchEngine(_data(), _stream_cfg())
    assert not hasattr(eng, "stats")
    assert not hasattr(SearchEngine, "stats")
    assert eng.metrics().engine.streaming is True


def test_latency_section_and_histogram_rendering():
    """A traced engine grows latency.* names in flatten() and a proper
    Prometheus histogram (_bucket/_sum/_count) in the text form."""
    eng = SearchEngine(_data(), ServeConfig(index="flat")).tracing()
    q = _rows(3, 8)
    for _ in range(5):
        eng.search(q, K)
    flat = eng.metrics().flatten()
    assert flat["latency.queries"] == 5
    for p in ("p50", "p95", "p99"):
        assert flat[f"latency.search.{p}"] > 0.0
    assert flat["latency.search.p50"] <= flat["latency.search.p99"]
    assert flat["latency.search.count"] == 5
    assert flat["latency.search.sum_ms"] > 0.0
    text = render_prometheus(eng.metrics())
    assert "# TYPE qpad_latency_search_seconds histogram" in text
    buckets = [int(m.group(1)) for m in re.finditer(
        r'qpad_latency_search_seconds_bucket\{le="[^"]+"\} (\d+)', text)]
    assert buckets == sorted(buckets)          # cumulative
    assert buckets[-1] == 5                    # +Inf holds every sample
    assert "qpad_latency_search_seconds_count 5" in text
    assert "qpad_latency_search_seconds_sum " in text


def test_recall_section_and_slow_query_capture():
    """Shadow-exact sampling feeds recall.estimate_at_k; a zero slow
    threshold captures every query into the ring with its knobs."""
    eng = build_engine(_data(), "ivf12x4>pq8x64>rr40").tracing(
        recall_every=1, slow_query_ms=0.0)
    q = _rows(3, 8)
    for _ in range(4):
        eng.search(q, K)
    m = eng.metrics()
    assert m.recall.samples == 4
    assert 0.0 < m.recall.estimate_at_k <= 1.0
    assert m.recall.k == K
    assert m.latency.slow_queries == 4
    assert m.latency.queries == 4
    ring = eng.tracer.slow_query_log()
    assert len(ring) == 4
    assert ring[-1]["k"] == K and ring[-1]["batch"] == 8
    assert ring[-1]["e2e_ms"] > 0.0
    text = render_prometheus(m)
    assert "qpad_recall_estimate_at_k" in text
    assert "# TYPE qpad_recall_estimate_at_k gauge" in text


def test_render_prometheus_text():
    eng = SearchEngine(_data(), _stream_cfg())
    eng.upsert(np.arange(600, 610, dtype=np.int32), _rows(1, 10))
    text = render_prometheus(eng.metrics())
    assert "# TYPE qpad_engine_compile_count counter" in text
    assert "# TYPE qpad_stream_fill gauge" in text
    assert "qpad_stream_delta_used 10" in text
    assert "qpad_compact_pending 0" in text    # bools render as 0/1
    assert 'engine_index="flat"' in text
    assert text.rstrip().splitlines()[-1].startswith("qpad_engine_info{")


def test_name_sanitization_and_label_escaping():
    """Dotted names with hostile characters become valid Prometheus
    names; label values with quotes/backslashes/newlines stay one
    well-formed line."""
    assert _sanitize_name("latency.search.p50") == "latency_search_p50"
    assert _sanitize_name("qpad.per-stage/scan") == "qpad_per_stage_scan"
    assert _sanitize_name("0weird") == "_0weird"
    assert _sanitize_name("ok_name:sub") == "ok_name:sub"
    assert _escape_label('a"b') == 'a\\"b'
    assert _escape_label("a\\b") == "a\\\\b"
    assert _escape_label("a\nb") == "a\\nb"
    # end-to-end: a spec string with every hostile character survives
    # the info line as one parseable sample
    text = render_prometheus(
        SearchEngine(_data(), ServeConfig(index="flat")).metrics())
    info = [ln for ln in text.splitlines()
            if ln.startswith("qpad_engine_info{")]
    assert len(info) == 1 and "\n" not in info[0]


# --- exposition lint ---------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})? '
    r'-?(\d+\.?\d*([eE][+-]?\d+)?|[+-]?Inf|NaN)$')


def _lint_exposition(text):
    """Minimal pure-python Prometheus text-format checker: every line is
    a comment or a well-formed sample; TYPE precedes its samples; each
    histogram's buckets are cumulative, end at +Inf, and agree with
    _count; no duplicate sample names outside histogram series."""
    typed, seen = {}, set()
    hist = {}
    for ln in text.splitlines():
        if not ln:
            continue
        if ln.startswith("# TYPE "):
            _, _, name, kind = ln.split(" ")
            assert name not in typed, f"duplicate TYPE for {name}"
            assert kind in ("counter", "gauge", "histogram"), ln
            typed[name] = kind
            continue
        if ln.startswith("#"):
            continue
        assert _SAMPLE_RE.match(ln), f"malformed sample line: {ln!r}"
        name = re.split(r"[{ ]", ln, maxsplit=1)[0]
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        if typed.get(base) == "histogram":
            series = hist.setdefault(base, {"buckets": [], "count": None})
            val = float(ln.rsplit(" ", 1)[1])
            if name.endswith("_bucket"):
                le = re.search(r'le="([^"]+)"', ln).group(1)
                series["buckets"].append((le, val))
            elif name.endswith("_count"):
                series["count"] = val
        else:
            assert typed.get(name), f"sample before TYPE: {ln!r}"
            key = ln.rsplit(" ", 1)[0]
            assert key not in seen, f"duplicate sample: {key!r}"
            seen.add(key)
    for base, series in hist.items():
        counts = [v for _, v in series["buckets"]]
        assert counts == sorted(counts), f"{base} buckets not cumulative"
        assert series["buckets"][-1][0] == "+Inf", f"{base} missing +Inf"
        assert counts[-1] == series["count"], f"{base} +Inf != _count"
    return typed


@pytest.mark.parametrize("spec", ("flat", "ivf12x4", "pq8x64",
                                  "ivf12x4>pq8x64>rr40"))
def test_exposition_lint_every_index_kind(spec):
    """The /metrics text of every index kind — traced, so the histogram
    series render too — passes the exposition lint."""
    eng = build_engine(_data(), spec).tracing(recall_every=2)
    q = _rows(3, 8)
    for _ in range(3):
        eng.search(q, K)
    typed = _lint_exposition(render_prometheus(eng.metrics()))
    assert typed.get("qpad_latency_search_seconds") == "histogram"
    assert typed.get("qpad_engine_compile_count") == "counter"


def test_metrics_server_serves_both_forms(tmp_path):
    """The --metrics-port endpoint: Prometheus text at /metrics, the
    flattened JSON at /metrics.json, 404 elsewhere — all consuming only
    the typed surface."""
    eng = SearchEngine(_data(), _stream_cfg()).durable(
        str(tmp_path / "live"), DurabilityConfig(fsync="batch"))
    eng.upsert(np.arange(600, 620, dtype=np.int32), _rows(1, 20))
    with MetricsServer(eng, port=0) as srv:
        assert srv.port > 0
        with urllib.request.urlopen(srv.url, timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert "qpad_wal_records" in body
        assert "# TYPE qpad_wal_fsyncs counter" in body
        base = f"http://{srv.host}:{srv.port}"
        with urllib.request.urlopen(base + "/metrics.json",
                                    timeout=10) as r:
            doc = json.loads(r.read().decode())
        assert doc["stream.delta_used"] == 20
        assert doc["wal.records"] >= 2
        assert doc["engine.role"] == "primary"
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(base + "/nope", timeout=10)
        assert exc.value.code == 404


def test_metrics_server_concurrent_scrapes_mid_traffic(tmp_path):
    """Scrapes racing live writes + traced searches: every response is a
    200 that passes the exposition lint — collect_metrics reads a
    consistent engine view and the Tracer's lock keeps the histogram
    internally consistent."""
    eng = SearchEngine(_data(), _stream_cfg(delta_capacity=256)).tracing(
        slow_query_ms=0.0)
    q = _rows(3, 8)
    eng.search(q, K)                           # warm the read program
    errors = []

    def scraper(url, n):
        try:
            for _ in range(n):
                with urllib.request.urlopen(url, timeout=10) as r:
                    assert r.status == 200
                    _lint_exposition(r.read().decode())
        except Exception as e:                 # pragma: no cover - surfaced
            errors.append(e)

    with MetricsServer(eng, port=0) as srv:
        ths = [threading.Thread(target=scraper, args=(srv.url, 8))
               for _ in range(4)]
        for t in ths:
            t.start()
        for i in range(6):                     # traffic while they scrape
            eng.upsert(np.arange(600 + 8 * i, 608 + 8 * i, dtype=np.int32),
                       _rows(4 + i, 8))
            eng.search(q, K)
        for t in ths:
            t.join()
    assert not errors
    m = eng.metrics()
    assert m.latency.queries == 7              # warmup + 6 in-loop
    assert m.stream.delta_used == 48
