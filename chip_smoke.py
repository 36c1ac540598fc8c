#!/usr/bin/env python3
"""Smoke run of the QPAD serving path on a TPU, through the public API.

Deployment: the ann-benchmarks SIFT-1M shape — 1,000,000 x 128 f32 corpus
rows plus held-out queries, both drawn from ``make_clustered`` under
``--seed``; L2 distance, k=10; the README pipeline
``qpad32>ivf256x4>pq16x256>rr64``. The corpus is 512 MB on the device.

Phases (one process; every check raises, nothing is caught and skipped):

  1. device      platform, device kind, device count, jax version;
  2. jnp         build_engine + SearchEngine.search at batches 1, 64 and 256
                 (small-batch bucket, compact scan, padded scan); recall@10
                 against the exact oracle (``knn_search_blocked``, HIGHEST
                 precision) must reach RECALL_FLOOR;
  3. kernel      the same engine on ``pq16x256@kernel`` with f32 and int8
                 LUTs: the compiled program must hold ``tpu_custom_call`` (on
                 a TPU), and its ids must agree with the jnp backend on at
                 least AGREE_FLOOR of the slots;
  4. streaming   ``.streaming(StreamConfig(delta_capacity=1024))``: upsert
                 4 x 256 fresh rows, delete 64 of them, compact, search;
                 every surviving row is its own top-1, no deleted id comes
                 back, and nothing compiles after the warm-up cycle.

``--chips 4`` runs only the sharded path and what it is compared with: the
engine is searched unsharded on device 0, then ``eng.shard(
make_serving_mesh(4), donate=True)`` and searched again with the jnp and
``@kernel`` backends; the ids must be identical, and every sharded leaf
must span the four chips.

Times printed here are smoke figures (first call = compile, then a steady
median of a few calls); they are not benchmark results.

The last stdout line is ``{"ok": true, "device": {...}}`` only when every
phase passed on a TPU. Without a TPU the script exits 1 at phase 1; with
``--rehearse`` it runs every phase on whatever platform JAX has (e.g.
``JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --rows 20000``, where
the kernels are interpreted) and still exits non-zero with no ``ok`` line.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import statistics
import sys
import time

SPEC = "qpad32>ivf256x4>pq16x256>rr64"
DIM = 128
K = 10
BATCHES = (1, 64, 256)
REPS = 3                   # steady-state calls per batch size
KERNEL_QUERIES = 64        # queries compared across ADC backends
# recall@10 of phase 2; the CPU rehearsal reads 0.6551 at 20,000 rows and
# 0.6563 at 200,000 (seed 0), and the floor leaves room for a TPU's
# default-precision projection and probe matmuls
RECALL_FLOOR = 0.60
AGREE_FLOOR = 0.99         # share of jnp/kernel id slots that must agree
STREAM_BATCH, STREAM_BATCHES, STREAM_DELETES = 256, 4, 64


class SmokeFailure(SystemExit):
    """A failed check: exits non-zero with the reason on stderr."""


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(f"FAIL: {what}")


def say(phase: str, msg: str):
    print(f"[{phase}] {msg}", flush=True)


def _parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase without a TPU (no ok line)")
    return ap.parse_args()


def _timed(fn):
    """(result, seconds) of one call that ends on the device."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _agreement(a, b) -> float:
    """Mean share of one row's ids found in the other's (ties may swap)."""
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    hits = [len(set(x) & set(y)) / a.shape[1] for x, y in zip(a, b)]
    return float(np.mean(hits))


def _with(eng, **knobs):
    """Re-key the engine's search knobs (the state is untouched)."""
    eng.config = dataclasses.replace(eng.config, **knobs)
    return eng


def phase_jnp(eng, corpus, queries):
    from repro.search import knn_search_blocked, recall_at_k
    ids_by_batch = {}
    for b in BATCHES:
        q = queries[:b]
        (_, ids), first = _timed(lambda: eng.search(q, K))
        steady = [_timed(lambda: eng.search(q, K))[1] for _ in range(REPS)]
        ids_by_batch[b] = ids
        say("2 jnp", f"batch {b:3d}: first call {first * 1e3:.1f} ms, "
            f"steady median {statistics.median(steady) * 1e3:.3f} ms "
            f"(bucket {eng.last_bucket}; smoke figures, not benchmark "
            "results)")
    (_, truth), t_oracle = _timed(
        lambda: knn_search_blocked(queries, corpus, K, block=8192))
    full = ids_by_batch[max(BATCHES)]
    rec = float(recall_at_k(full, truth))
    say("2 jnp", f"recall@{K} = {rec:.4f} over {queries.shape[0]} queries "
        f"(exact oracle {t_oracle:.1f} s); floor {RECALL_FLOOR}")
    for b in BATCHES[:-1]:
        say("2 jnp", f"batch {b} vs the same queries in batch "
            f"{max(BATCHES)}: id agreement "
            f"{_agreement(ids_by_batch[b], full[:b]):.4f}")
    check(rec >= RECALL_FLOOR, f"recall@{K} {rec:.4f} < {RECALL_FLOOR}")


def phase_kernel(eng, queries, on_tpu: bool):
    import jax
    from repro.search import search_fn
    q = queries[:KERNEL_QUERIES]
    nprobe = eng.config.nprobe
    for lut in ("f32", "int8"):
        program = jax.jit(functools.partial(
            search_fn, k=K, nprobe=nprobe, rerank=eng.config.rerank,
            backend="kernel", lut_dtype=lut))
        hlo = program.lower(eng.state, q).compile().as_text()
        custom = "tpu_custom_call" in hlo
        say("3 kernel", f"lut {lut}: compiled search program "
            f"{'holds' if custom else 'lacks'} tpu_custom_call")
        if on_tpu:
            check(custom, f"lut {lut}: the @kernel program on a TPU holds "
                  "no tpu_custom_call (the kernel was interpreted)")
        (_, ids_j), t_j = _timed(
            lambda: _with(eng, pq_backend="jnp", lut_dtype=lut).search(q, K))
        (_, ids_k), t_k = _timed(
            lambda: _with(eng, pq_backend="kernel",
                          lut_dtype=lut).search(q, K))
        agree = _agreement(ids_j, ids_k)
        say("3 kernel", f"lut {lut}: jnp/kernel id agreement {agree:.4f} "
            f"over {q.shape[0]} queries x {K} (first calls: jnp "
            f"{t_j * 1e3:.0f} ms, kernel {t_k * 1e3:.0f} ms; smoke figures)")
        check(agree >= AGREE_FLOOR,
              f"lut {lut}: jnp/kernel agreement {agree:.4f} < {AGREE_FLOOR}")
    _with(eng, pq_backend="jnp", lut_dtype="f32")


def phase_stream(eng, rows: int, seed: int):
    import jax
    import numpy as np
    from repro.data.synthetic import make_clustered
    from repro.search import StreamConfig
    eng.streaming(StreamConfig(delta_capacity=1024))
    n_new = STREAM_BATCH * STREAM_BATCHES
    fresh, _ = make_clustered(jax.random.key(seed + 1), n_new, 1, DIM,
                              n_clusters=_clusters(rows))
    fresh = np.asarray(fresh)
    new_ids = np.arange(rows, rows + n_new, dtype=np.int32)
    batches = np.split(np.arange(n_new), STREAM_BATCHES)
    dead = np.concatenate([b[:STREAM_DELETES // STREAM_BATCHES]
                           for b in batches])

    def cycle(batch_ix, deletes):
        for ix in batch_ix:
            eng.upsert(new_ids[ix], fresh[ix])
        eng.delete(new_ids[deletes])
        eng.compact()

    # warm-up cycle: one of each program (upsert, delete, compact, search)
    t0 = time.perf_counter()
    cycle(batches[:1], dead[dead < STREAM_BATCH])
    jax.block_until_ready(eng.search(fresh[:KERNEL_QUERIES], K))
    warm = eng.compile_count
    cycle(batches[1:], dead[dead >= STREAM_BATCH])
    top1 = np.concatenate([
        np.asarray(eng.search(fresh[i:i + KERNEL_QUERIES], K)[1])
        for i in range(0, n_new, KERNEL_QUERIES)])
    say("4 stream", f"{n_new} upserts, {dead.size} deletes, 2 compactions "
        f"in {time.perf_counter() - t0:.1f} s (smoke figure); "
        f"compile_count {warm} -> {eng.compile_count}, "
        f"grow_count {eng.grow_count}")
    live = np.setdiff1d(np.arange(n_new), dead)
    self_hit = float(np.mean(top1[live, 0] == new_ids[live]))
    leaked = np.isin(top1, new_ids[dead]).sum()
    say("4 stream", f"surviving rows returned as their own top-1: "
        f"{self_hit:.4f}; deleted ids returned: {int(leaked)}")
    check(self_hit == 1.0, "a surviving upserted row is not its own top-1")
    check(leaked == 0, "a deleted id came back")
    check(eng.compile_count == warm,
          f"compile_count moved after warm-up ({warm} -> "
          f"{eng.compile_count})")


def phase_sharded(eng, queries, shards: int):
    import jax
    import numpy as np
    from repro.launch.mesh import make_serving_mesh
    q = queries[:KERNEL_QUERIES]
    backends = ("jnp", "kernel")
    # host copies (np.array, not np.asarray: on a CPU rehearsal asarray
    # would keep the device buffers alive and read as left behind)
    dense = {b: np.array(_with(eng, pq_backend=b).search(q, K)[1])
             for b in backends}
    mesh = make_serving_mesh(shards)
    eng.shard(mesh, donate=True)
    jax.block_until_ready(eng.sharded_state)
    first = mesh.devices.flat[0]
    gc.collect()
    stray = [a for a in jax.live_arrays() if a.sharding.device_set == {first}
             and a is not queries and a is not q]
    check(not stray, "arrays left on the first chip alone: "
          f"{[(a.shape, str(a.dtype)) for a in stray]}")
    devices = set(mesh.devices.flat)
    leaves = jax.tree.leaves(eng.sharded_state)
    for leaf in leaves:
        check(set(leaf.sharding.device_set) == devices,
              f"a sharded leaf {leaf.shape} lives on "
              f"{sorted(d.id for d in leaf.sharding.device_set)}, not on "
              f"all {shards} chips")
        shard_devs = [s.device for s in leaf.addressable_shards]
        check(len(set(shard_devs)) == len(shard_devs),
              f"two shards of a leaf {leaf.shape} share a chip")
    split = [leaf for leaf in leaves
             if leaf.addressable_shards[0].data.shape != leaf.shape]
    check(bool(split), "no leaf is split across the chips")
    check(eng.state is None, "the dense single-chip state was kept")
    say("S sharded", f"{len(leaves)} leaves on {shards} chips, "
        f"{len(split)} split by row/cell (per-chip corpus rows: "
        f"{eng.sharded_state.corpus.addressable_shards[0].data.shape[0]})")
    def in_use():
        return [(d.memory_stats() or {}).get("bytes_in_use")
                for d in mesh.devices.flat]
    say("S sharded", f"bytes_in_use per chip (arrays and loaded programs): "
        f"{in_use()}")
    jax.clear_caches()           # unload every compiled program
    gc.collect()
    say("S sharded", f"bytes_in_use per chip with no program loaded: "
        f"{in_use()}")
    for b in backends:
        (_, ids), first = _timed(
            lambda: _with(eng, pq_backend=b).search(q, K))
        same = bool((np.asarray(ids) == dense[b]).all())
        say("S sharded", f"backend {b}: ids identical to unsharded: {same} "
            f"({q.shape[0]} queries; first call {first * 1e3:.0f} ms, "
            "smoke figure)")
        check(same, f"backend {b}: sharded ids differ from unsharded")


def _clusters(rows: int) -> int:
    """Mixture components: one per ~256 rows, so neighbourhoods keep their
    size as the corpus is cut for a rehearsal."""
    return max(16, rows // 256)


def main():
    args = _parse_args()
    import jax
    import numpy as np
    dev = jax.devices()[0]
    count = len(jax.devices())
    say("1 device", f"platform={dev.platform} kind={dev.device_kind} "
        f"count={count} jax={jax.__version__}")
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        raise SmokeFailure(f"FAIL: no TPU (platform {dev.platform}); "
                           "--rehearse runs the phases anyway")
    check(count >= args.chips, f"--chips {args.chips} needs {args.chips} "
          f"devices, found {count}")

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.data.synthetic import make_clustered
    from repro.search import build_engine
    say("1 device", f"compile cache: {enable_compile_cache()}")

    corpus, queries = make_clustered(
        jax.random.key(args.seed), args.rows, args.queries, DIM,
        n_clusters=_clusters(args.rows))
    if args.chips > 1:
        # a host copy: the engine then owns its corpus, and shard(donate=
        # True) can release every dense buffer on the first chip
        corpus = np.asarray(corpus)
    t0 = time.perf_counter()
    eng = build_engine(corpus, SPEC, seed=args.seed)
    jax.block_until_ready(eng.state)
    t_build = time.perf_counter() - t0
    say("2 jnp", f"built {SPEC} over {args.rows} x {DIM} in "
        f"{t_build:.1f} s (smoke figure)")
    if args.chips > 1:
        del corpus
        phase_sharded(eng, queries, args.chips)
    else:
        phase_jnp(eng, corpus, queries)
        phase_kernel(eng, queries, on_tpu)
        phase_stream(eng, args.rows, args.seed)
    if not on_tpu:
        raise SmokeFailure(f"rehearsal on {dev.platform} passed every "
                           "phase; no ok line without a TPU")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))


if __name__ == "__main__":
    main()
