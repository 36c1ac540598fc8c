"""Serving launcher: build an MPAD-reduced vector index over a corpus and
serve batched k-NN queries (the paper's deployment shape).

  PYTHONPATH=src python -m repro.launch.serve --corpus 20000 --dim 256 \
      --spec "qpad32>ivf64x8>rr40" --batches 5

The pipeline is declared either with ``--spec`` (the index-spec grammar:
``qpad<m> > ivf<nlist>x<nprobe> > pq<M>x<K>[:f32|bf16|i8][@jnp|kernel] >
rr<n>``) or with the individual legacy flags (``--index``/``--nlist``/...),
which are lowered onto the same spec. ``--snapshot-dir`` exercises the
persistence lifecycle: the built engine is saved and re-loaded before
serving.

Durable streaming: ``--stream --durable DIR`` snapshots the engine to DIR
and write-ahead-logs every mutation (``--fsync`` picks the durability/
throughput trade-off; ``--group-commit-ms`` coalesces ``--fsync always``
bursts into shared fsyncs), then serves from the crash-recovered engine;
``--background-compact`` folds the delta on a worker thread instead of
blocking searches.

Observability: ``--metrics-port N`` serves the engine's typed metrics
snapshot (``SearchEngine.metrics()``) from a stdlib http.server thread —
``GET /metrics`` is Prometheus text, ``GET /metrics.json`` the flattened
JSON (port 0 binds an ephemeral port and prints it). Request-level
tracing rides the same engine: ``--slow-query-ms T`` captures
over-threshold queries into a ring buffer and ``--recall-every N``
shadow-checks 1-in-N batches against the exact scan to estimate live
recall — either turns on the ``latency.*`` histograms in the scrape.
``--trace-dir DIR`` profiles the serving run with ``jax.profiler`` into
DIR: the engine's program spans (``qpad.search.prepare`` / ``.launch``,
``qpad.upsert``, ``qpad.compact.fold``, ...) and the device ops, on one
clock (open the ``.xplane.pb`` in TensorBoard, Perfetto or xprof).

Sharded serving: ``--shards N`` partitions the engine state over an N-way
data mesh (``--mesh host`` simulates the N devices on the CPU backend —
useful for dry-runs; it sets ``JAX_PLATFORMS=cpu`` and ``XLA_FLAGS`` before
the first jax call, so it never asks a TPU host for devices it lacks).

The ADC kernel (``@kernel`` in a spec) runs compiled on a TPU and in the
Pallas interpreter elsewhere; the platform decides, no flag does. The
persistent compilation cache follows ``JAX_COMPILATION_CACHE_DIR`` when it
is set, else ``<checkout>/.jax_cache`` (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--corpus", type=int, default=20000)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--spec", default=None,
                    help="index pipeline spec string, e.g. "
                         "'qpad32>ivf64x8>pq8x256:i8' — overrides "
                         "--target-dim/--index/--nlist/--nprobe/"
                         "--pq-subspaces/--lut-dtype/--pq-backend")
    ap.add_argument("--target-dim", type=int, default=32,
                    help="MPAD reduction target (0 = no reduction)")
    ap.add_argument("--reducer", choices=["qpad", "pca", "mlp"],
                    default="qpad",
                    help="Reduce-stage kind (the reducer zoo; ignored "
                         "when --target-dim is 0)")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--batches", type=int, default=5)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--index", choices=["flat", "ivf", "pq", "opq",
                                        "ivfpq"],
                    default="flat")
    ap.add_argument("--nlist", type=int, default=64)
    ap.add_argument("--nprobe", type=int, default=8)
    ap.add_argument("--pq-subspaces", type=int, default=8)
    ap.add_argument("--lut-dtype", choices=["f32", "bf16", "int8"],
                    default="f32",
                    help="ADC lookup-table precision (pq/ivfpq)")
    ap.add_argument("--pq-backend", choices=["jnp", "kernel"], default="jnp",
                    help="ADC scoring backend (kernel = fused Pallas scan)")
    ap.add_argument("--query-bucket", type=int, default=64,
                    help="min padded query-batch size; ragged batches round "
                         "up to powers of two and share compilations")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="save the built engine to DIR and serve from the "
                         "re-loaded snapshot (persistence smoke)")
    ap.add_argument("--shards", type=int, default=0,
                    help="partition EngineState over this many devices "
                         "(data-parallel sharded serving; 0 = single-device)")
    ap.add_argument("--mesh", choices=["device", "host"], default="device",
                    help="mesh device source: 'device' = the real jax "
                         "devices; 'host' = simulate --shards CPU devices "
                         "(JAX_PLATFORMS=cpu + "
                         "--xla_force_host_platform_device_count)")
    ap.add_argument("--donate", action="store_true",
                    help="with --shards: release the dense EngineState "
                         "once the sharded copy is placed (no 2x memory)")
    ap.add_argument("--stream", action="store_true",
                    help="mutable serving: interleave a 90/10 read/write "
                         "workload (upserts into the delta segment, "
                         "tombstoned deletes, auto-compaction)")
    ap.add_argument("--delta-capacity", type=int, default=512,
                    help="--stream: delta segment size (rows)")
    ap.add_argument("--write-batch", type=int, default=64,
                    help="--stream: rows per upsert batch")
    ap.add_argument("--durable", default=None, metavar="DIR",
                    help="--stream: make the engine durable — snapshot to "
                         "DIR, write-ahead log every mutation, and reopen "
                         "via crash recovery (load_engine) before serving")
    ap.add_argument("--fsync", choices=["always", "batch", "never"],
                    default="batch",
                    help="--durable: WAL fsync mode (default batch)")
    ap.add_argument("--background-compact", action="store_true",
                    help="--stream: fold the delta on a worker thread and "
                         "swap atomically instead of blocking searches")
    ap.add_argument("--group-commit-ms", type=float, default=0.0,
                    help="--durable --fsync always: coalesce concurrent "
                         "WAL appends into shared fsyncs, waiting at most "
                         "this long to gather a batch (0 = one fsync per "
                         "record)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve SearchEngine.metrics() over HTTP from a "
                         "background thread: /metrics (Prometheus text), "
                         "/metrics.json (JSON); 0 = ephemeral port")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="profile the serving run into DIR with "
                         "jax.profiler: program spans and device ops on "
                         "one clock (an .xplane.pb)")
    ap.add_argument("--slow-query-ms", type=float, default=None, metavar="T",
                    help="capture searches slower than T ms into the "
                         "tracer's slow-query ring buffer (printed at "
                         "the end of the run)")
    ap.add_argument("--recall-every", type=int, default=0, metavar="N",
                    help="shadow-check 1-in-N batches against an exact "
                         "brute-force scan and maintain the "
                         "recall.estimate_at_k gauge (0 = off)")
    return ap.parse_args()


def _spec_from_flags(args):
    """Lower the legacy flags onto a pipeline spec (one build path; the
    stages are constructed directly so the grammar lives only in
    ``repro.search.spec``). Import deferred: must run after the XLA_FLAGS
    setup in ``main``."""
    from repro.search import Coarse, Code, IndexSpec, Reduce, Rerank
    return IndexSpec(
        reduce=(Reduce(args.target_dim, kind=args.reducer)
                if args.target_dim else None),
        coarse=(Coarse(nlist=args.nlist, nprobe=args.nprobe)
                if args.index in ("ivf", "ivfpq") else None),
        code=(Code(kind="opq" if args.index == "opq" else "pq",
                   subspaces=args.pq_subspaces, centroids=256,
                   lut_dtype=args.lut_dtype, backend=args.pq_backend)
              if args.index in ("pq", "opq", "ivfpq") else None),
        rerank=Rerank(4 * args.k))


def main():
    args = _parse_args()
    if args.shards and args.mesh == "host":
        # must land before jax initializes its backend (first device use);
        # the CPU platform is forced too, or a TPU host would hand out its
        # real chips and the simulated count would never apply
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.shards}")

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.core import MPADConfig
    from repro.data.synthetic import make_clustered
    from repro.launch.mesh import make_serving_mesh
    from repro.search import (StreamConfig, build_engine, format_spec,
                              jax_profile, knn_search, load_engine,
                              parse_spec)
    from repro.search.knn import recall_at_k

    spec = parse_spec(args.spec) if args.spec else _spec_from_flags(args)
    key = jax.random.key(0)
    corpus, _ = make_clustered(key, args.corpus, 1, args.dim, n_clusters=64,
                               spread=0.4, center_scale=1.5)
    t0 = time.time()
    runtime = dict(query_bucket=args.query_bucket, fit_sample=4096)
    if args.stream:
        runtime["stream"] = StreamConfig(
            delta_capacity=args.delta_capacity,
            background_compact=args.background_compact)
    if spec.reduce is not None and spec.reduce.kind == "qpad":
        # the MPAD knobs configure the qpad kind only; other reducers
        # own their training hyperparameters
        runtime["mpad"] = MPADConfig(m=spec.reduce.m, iters=64,
                                     batch_size=2048)
    engine = build_engine(corpus, spec, **runtime)
    print(f"index built in {time.time()-t0:.1f}s "
          f"(spec={format_spec(spec)}, kind={spec.kind}"
          + (f", streaming delta={args.delta_capacity}" if args.stream
             else "") + ")")
    if args.durable:
        from repro.search import DurabilityConfig
        t0 = time.time()
        engine.durable(args.durable, DurabilityConfig(
            fsync=args.fsync, group_commit_ms=args.group_commit_ms))
        # reopen through the recovery path so the launcher exercises the
        # same snapshot+replay an operator would see after a crash
        engine = load_engine(args.durable)
        print(f"durable via {args.durable} in {time.time()-t0:.1f}s "
              f"(fsync={args.fsync}"
              + (f", group_commit_ms={args.group_commit_ms}"
                 if args.group_commit_ms else "")
              + "; every write WAL-logged, served from the recovered "
              "engine)")
    if args.snapshot_dir:
        t0 = time.time()
        engine.save(args.snapshot_dir)
        engine = load_engine(args.snapshot_dir)
        print(f"snapshot round-trip via {args.snapshot_dir} in "
              f"{time.time()-t0:.1f}s (serving from the restored engine)")
    if args.shards:
        mesh = make_serving_mesh(args.shards)
        engine.shard(mesh, donate=args.donate)
        print(f"engine sharded over mesh {dict(mesh.shape)} "
              f"({args.corpus} rows -> ~{-(-args.corpus // args.shards)} "
              "per shard"
              + (", dense state donated" if args.donate else "") + ")")
    tracing_on = (args.slow_query_ms is not None or args.recall_every
                  or args.metrics_port is not None)
    if tracing_on:
        # attach to the FINAL engine object (post durable/snapshot/shard
        # swap-outs) so the tracer sees the served programs
        engine.tracing(slow_query_ms=args.slow_query_ms,
                       recall_every=args.recall_every)
        knobs = ["histograms"]
        if args.slow_query_ms is not None:
            knobs.append(f"slow_query_ms={args.slow_query_ms}")
        if args.recall_every:
            knobs.append(f"recall_every={args.recall_every}")
        print(f"tracing on ({', '.join(knobs)})")
    metrics_srv = None
    if args.metrics_port is not None:
        from repro.search import MetricsServer
        metrics_srv = MetricsServer(engine, port=args.metrics_port)
        print(f"metrics at {metrics_srv.url} (Prometheus text; "
              f"/metrics.json for JSON)")

    total, rec_sum = 0.0, 0.0
    write_s, rows_written = 0.0, 0
    next_id = args.corpus
    import numpy as np
    profile = (jax_profile(args.trace_dir) if args.trace_dir is not None
               else contextlib.nullcontext())
    with profile:
        for i in range(args.batches):
            queries = corpus[jax.random.randint(
                jax.random.fold_in(key, i), (args.batch,), 0, args.corpus)]
            if args.stream:
                # the 10% write leg: upsert a batch of perturbed rows under
                # fresh ids, plus a few deletes — all served from the delta /
                # tombstones, auto-compacting at the threshold
                wb = args.write_batch
                vecs = corpus[:wb] + 0.01 * jax.random.normal(
                    jax.random.fold_in(key, 1000 + i), (wb, args.dim))
                t0 = time.time()
                engine.upsert(np.arange(next_id, next_id + wb), vecs)
                if next_id > args.corpus:         # only delete rows WE streamed
                    engine.delete(np.arange(next_id - wb,
                                            next_id - wb + wb // 8))
                jax.block_until_ready(engine.store.delta_count)
                write_s += time.time() - t0
                rows_written += wb
                next_id += wb
            t0 = time.time()
            _, ids = engine.search(queries, args.k)
            jax.block_until_ready(ids)
            dt = time.time() - t0
            _, truth = knn_search(queries, corpus, args.k)
            rec = float(recall_at_k(ids, truth))
            total += dt
            rec_sum += rec
            print(f"batch {i}: {dt*1e3:7.1f} ms  recall@{args.k}={rec:.4f}")
            if i == 0 and metrics_srv is not None and tracing_on:
                # mid-traffic scrape: the histogram series must already be
                # live after the first batch (the CI smoke greps for it)
                import urllib.request
                with urllib.request.urlopen(metrics_srv.url, timeout=5) as r:
                    mid = r.read().decode().splitlines()
                hist = [ln for ln in mid
                        if ln.startswith("qpad_latency_search_seconds")]
                print(f"mid-traffic scrape: {len(mid)} lines, "
                      f"{len(hist)} latency-histogram samples")
                for line in hist[:3]:
                    print(f"  {line}")
    if args.trace_dir is not None:
        print(f"profile written under {args.trace_dir}")
    print(f"\nmean: {total/args.batches*1e3:.1f} ms/batch "
          f"({args.batch/(total/args.batches):.0f} qps), "
          f"recall={rec_sum/args.batches:.4f}")
    if args.stream and write_s:
        print(f"writes: {rows_written} rows in {write_s:.2f}s "
              f"({rows_written/write_s:.0f} rows/s), "
              f"grow_count={engine.grow_count}")
        t0 = time.time()
        engine.compact()
        print(f"final compact: {time.time()-t0:.2f}s "
              f"(base rows={int(engine.store.n_rows)})")
        m = engine.metrics()
        if m.wal is not None:
            print(f"wal: {m.wal.records} records / {m.wal.bytes} bytes / "
                  f"{m.wal.fsyncs} fsyncs"
                  + (f" ({m.wal.group_commits} group commits)"
                     if m.wal.group_commits else "")
                  + f", {m.wal.replayed} replayed; "
                  f"compactions={m.compact.compactions} "
                  f"vacuums={m.compact.vacuums} "
                  f"rebuilds={m.compact.rebuilds}")
    if tracing_on:
        flat = engine.metrics().flatten()
        print(f"latency: p50={flat['latency.search.p50']:.2f}ms "
              f"p95={flat['latency.search.p95']:.2f}ms "
              f"p99={flat['latency.search.p99']:.2f}ms over "
              f"{flat['latency.queries']} traced searches")
        if args.recall_every:
            est = flat.get("recall.estimate_at_k")
            if est is not None:
                print(f"recall estimate: {est:.4f}@{flat['recall.k']} "
                      f"({flat['recall.samples']} shadow samples)")
        if args.slow_query_ms is not None:
            log = engine.tracer.slow_query_log()
            print(f"slow queries (>{args.slow_query_ms}ms): "
                  f"{flat['latency.slow_queries']} captured, "
                  f"{len(log)} in the ring")
            for entry in log[-3:]:
                print(f"  seq={entry['seq']} {entry['e2e_ms']:.2f}ms "
                      f"batch={entry['batch']} bucket={entry['bucket']} "
                      f"nprobe={entry['nprobe']} spec={entry['spec']}")
    if metrics_srv is not None:
        import urllib.request
        with urllib.request.urlopen(metrics_srv.url, timeout=5) as r:
            sample = r.read().decode().splitlines()
        print("sample scrape (/metrics):")
        for line in sample[:8]:
            print(f"  {line}")
        metrics_srv.close()


if __name__ == "__main__":
    main()
