"""One run of one cell: set-up, warm-up, the measured window, the
comparison that decides ``correct``, and the result line.

Phases, in order:

  set-up    data from the seed on the device, the engine built from the
            configuration, every program the window uses warmed once
            (``setup_s`` runs from process start to the window's first
            request);
  window    the traffic mix for ``seconds``, with no compile allowed: a
            compile or trace inside it fails the run;
  after     device memory peak read, the engine's state freed, then the
            plain reference over the rows live at each search.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import check, trace as tracing
from harness.servers import EngineServer, ReferenceServer
from harness.spec import BENCH_DIR, Cell
from harness.traffic import DELETE, SEARCH, UPSERT, make_schedule
from reference.adc_work import adc_work, probed_rows, rerank_work
from reference.data import make_data

__all__ = ["RunFailure", "Options", "Context", "run_cell", "read_metrics",
           "device_info", "say"]

TRACE_DIR = BENCH_DIR / ".cache" / "trace"
# events that mean a program is traced, lowered or compiled
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


class RunFailure(SystemExit):
    """A run that cannot give a result: exits non-zero, prints no line."""

    def __init__(self, why: str):
        super().__init__(f"bench: {why}")


def say(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Options:
    seed: int
    seconds: float
    trace: bool = False
    rehearse: bool = False         # any platform, the configuration's
    #                                rehearsal sizes; never a device metric
    control: bool = False          # the bf16 reference in the program's place
    rate_per_s: float | None = None    # knee sweeps: override the mix's rate
    server_wrap: object = None     # tests: wrap the server (plant a fault)
    t0: float = dataclasses.field(     # process start (perf_counter)
        default_factory=time.perf_counter)


def device_info(chips: int, rehearse: bool) -> dict:
    """Platform, kind and count as JAX reports them; fails without a TPU
    (unless rehearsing) or with fewer chips than the cell asks for."""
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" and not rehearse:
        raise RunFailure(f"no TPU: JAX found {d0.platform} "
                         f"({d0.device_kind}); the benchmark does not fall "
                         "back to another platform")
    if len(devs) < chips:
        raise RunFailure(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


class _CompileWatch:
    """Records jax.monitoring durations while entered: ``events`` lists
    the traces and compiles, ``totals`` every duration event by name."""

    def __init__(self):
        self.events, self.totals = [], {}

    def _on(self, name, secs, **_):
        if name in _COMPILE_EVENTS:
            self.events.append(name)
        n, t = self.totals.get(name, (0, 0.0))
        self.totals[name] = (n + 1, t + secs)

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)

    def summary(self) -> str:
        short = {"/jax/core/compile/backend_compile_duration": "compiles",
                 "/jax/compilation_cache/cache_retrieval_time_sec":
                     "cache loads",
                 "/jax/core/compile/jaxpr_trace_duration": "traces"}
        return ", ".join(f"{n} {label} {t:.3f} s" for key, label in
                         short.items() for n, t in [self.totals.get(
                             key, (0, 0.0))])


class _GcWatch:
    """Python's garbage-collector pauses while entered: a host stall the
    window's tail would otherwise leave unexplained."""

    def __init__(self):
        self.pauses, self._t = [], 0.0

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def summary(self) -> str:
        full = [p for g, p in self.pauses if g == 2]
        longest = max((p for _, p in self.pauses), default=0.0)
        return (f"gc {len(self.pauses)} collections ({len(full)} full), "
                f"longest {longest * 1e3:.3f} ms")


@dataclasses.dataclass
class _Req:
    kind: int
    due: float                     # seconds after the window opened
    call: float = 0.0
    ret: float = 0.0               # the unblocked call returned
    done: float = 0.0              # result ready on the device
    ids: np.ndarray | None = None
    dists: np.ndarray | None = None
    key: np.ndarray | None = None  # what each query asked (check.Answers)
    lo: int = 0
    hi: int = 0
    own: int = -1


def _effective_config(cell: Cell, rehearse: bool) -> dict:
    """The configuration as run: with ``rehearse``, its ``rehearse``
    groups (shape, engine, stream, check) laid over the full ones."""
    config = {k: v for k, v in cell.config.items() if k != "rehearse"}
    if rehearse:
        for group, over in cell.config.get("rehearse", {}).items():
            config[group] = {**config.get(group, {}), **over}
    return config


def _warm_searches(server, pool, batch: int, calls: int = 2):
    for i in range(calls):
        jax.block_until_ready(server.search(pool[i * batch:(i + 1) * batch]))


def _warm_stream(server, live, rows_of, write_rows, pool, upserts: int):
    """Upserts and deletes, each followed by a search, until one
    background compaction has been begun, folded and swapped in, then one
    more of each: every write-path program and its copies compile here.
    Uses at most ``upserts`` upserts."""
    eng = server.engine
    spent = {"upsert": 0.0, "delete": 0.0, "search": 0.0, "settle": 0.0}

    def step(name, fn, *a):
        t = time.perf_counter()
        jax.block_until_ready(fn(*a))
        spent[name] += time.perf_counter() - t

    def cycle():
        step("upsert", _write, server, live, rows_of, write_rows, UPSERT)
        step("delete", _write, server, live, rows_of, write_rows, DELETE)
        step("search", server.search, pool[:1])

    start = eng.metrics().compact.compactions
    for _ in range(upserts - 1):
        cycle()
        if eng.metrics().compact.pending:
            step("settle", server.settle)
        if eng.metrics().compact.compactions > start:
            break
    else:
        raise RunFailure(f"warm-up ran {upserts - 1} upserts and no "
                         "compaction came; raise warm_upserts")
    cycle()
    step("settle", server.settle)
    say("stream warm-up: " + ", ".join(f"{k} {v:.3f} s"
                                       for k, v in spent.items()))


class _Live:
    """Live ids [lo, hi): upserts take the next ids, deletes the oldest."""

    def __init__(self, n):
        self.lo, self.hi = 0, n


def _write(server, live, rows_of, write_rows, kind):
    if kind == UPSERT:
        ids = np.arange(live.hi, live.hi + write_rows, dtype=np.int32)
        out = server.upsert(ids, rows_of(ids))
        live.hi += write_rows
    else:
        ids = np.arange(live.lo, live.lo + write_rows, dtype=np.int32)
        out = server.delete(ids)
        live.lo += write_rows
    return ids, out


def _span(name: str, on: bool):
    return (jax.profiler.TraceAnnotation(name) if on
            else contextlib.nullcontext())


def _wait_until(t: float):
    while (rem := t - time.perf_counter()) > 5e-4:
        time.sleep(rem - 5e-4)
    while time.perf_counter() < t:
        pass


def _serve(req, t_open, trace_on, name, call, *args):
    """Time one request: ``call(*args)`` under the span ``name``, then the
    wait until what it returned is ready; times are after ``t_open``."""
    with _span(name, trace_on):
        req.call = time.perf_counter() - t_open
        out = call(*args)
        req.ret = time.perf_counter() - t_open
    with _span("bench.block", trace_on):
        jax.block_until_ready(out)
        req.done = time.perf_counter() - t_open
    return out


def _search(server, req, q, trace_on, t_open=0.0):
    d, i = _serve(req, t_open, trace_on, "bench.search", server.search, q)
    req.ids, req.dists = np.asarray(i), np.asarray(d)


def _window_closed(server, sched, pool, seconds, trace_on, n_rows):
    """One client: the next request goes when the last has returned."""
    reqs, j = [], 0
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < seconds:
        rows = sched.closed_queries(j)
        req = _Req(SEARCH, time.perf_counter() - t_open, key=rows,
                   hi=n_rows)
        _search(server, req, pool[rows], trace_on, t_open)
        reqs.append(req)
        j += 1
    return reqs


def _window_open(server, sched, pool, inserts_h, live, rows_of, trace_on,
                 n_rows):
    """Requests sent when due, whether or not the last has returned; each
    is timed from when it was due."""
    reqs = []
    last_upsert = None
    t_open = time.perf_counter()
    for r in range(sched.due.shape[0]):
        req = _Req(int(sched.kind[r]), float(sched.due[r]))
        with _span("bench.wait", trace_on):
            _wait_until(t_open + req.due)
        if req.kind == SEARCH:
            req.lo, req.hi = live.lo, live.hi
            if sched.own_write[r] >= 0 and last_upsert is not None:
                row = int(last_upsert[sched.own_write[r]])
                req.own = row
                req.key = np.array([sched.pool_size + row - n_rows])
                q = inserts_h[row - n_rows][None, :]
            else:
                req.key = sched.queries[r]
                q = pool[req.key]
            _search(server, req, q, trace_on, t_open)
        else:
            name = "bench.upsert" if req.kind == UPSERT else "bench.delete"
            ids, _ = _serve(req, t_open, trace_on, name, _write, server,
                            live, rows_of, sched.write_rows, req.kind)
            if req.kind == UPSERT:
                last_upsert = ids
        reqs.append(req)
    return reqs


def _answers(reqs, pool_h, inserts_h, pool_size):
    parts = []
    for r in reqs:
        nq = r.ids.shape[0]
        qv = np.stack([pool_h[x] if x < pool_size
                       else inserts_h[x - pool_size] for x in r.key])
        parts.append(check.Answers(
            key=np.asarray(r.key, np.int64), queries=qv, ids=r.ids,
            dists=r.dists, lo=np.full(nq, r.lo), hi=np.full(nq, r.hi),
            own=np.full(nq, r.own)))
    return check.Answers.concat(parts)


class Context:
    """What a metric's reader reads: the window's requests with their host
    times, the reduced device trace (traced runs), the ADC work count, the
    comparison's readings, the device memory peak and the chip's peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def of_kind(self, kind: int) -> list:
        return [r for r in self.reqs if r.kind == kind]

    @property
    def searches(self) -> list:
        return self.of_kind(SEARCH)

    def latencies_s(self, kind: int) -> np.ndarray:
        """Due to done, for every request of ``kind`` in the window."""
        return np.array([r.done - r.due for r in self.of_kind(kind)])


def _peaks(kind: str) -> dict | None:
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)["kinds"]
    return table.get(kind)


class Served:
    """A cell set up: its data from the seed, the server built over it and
    warmed, and the live id range a stream cell's writes move."""

    def __init__(self, cell: Cell, opt: Options, upserts: int):
        self.cell, self.opt = cell, opt
        self.config = config = _effective_config(cell, opt.rehearse)
        shape = config["shape"]
        self.n_rows, dim = int(shape["rows"]), int(shape["dim"])
        self.pool_size = int(shape["queries"])
        self.streaming = "stream" in config
        self.write_rows = int(cell.traffic.get("write_rows", 0))
        # the rows a stream cell may upsert: all that its store can hold
        extra = (config["stream"]["row_capacity"] - self.n_rows
                 if self.streaming else 0)
        needed = (upserts + int(config.get("warm_upserts", 0))) \
            * self.write_rows
        if needed > extra and self.streaming:
            raise RunFailure(f"row_capacity {self.n_rows + extra} cannot "
                             f"hold {self.n_rows} rows and {needed} upserted")
        watch = _CompileWatch().__enter__()
        t = time.perf_counter()
        self.corpus, self.inserts, pool = _data(config)
        self.pool_h = np.asarray(pool)
        self.inserts_h = np.asarray(self.inserts)
        jax.block_until_ready(self.corpus)
        del pool
        self.t_gen = time.perf_counter() - t

        t = time.perf_counter()
        if opt.control:
            server = ReferenceServer(config, self.corpus, self.inserts)
        else:
            server = EngineServer(config, self.corpus)
        self.server = (opt.server_wrap(server) if opt.server_wrap is not None
                       else server)
        if self.streaming:   # the store holds its own copy: regenerated
            self.corpus = None
        self.t_build = time.perf_counter() - t
        self.live = _Live(self.n_rows)

        t = time.perf_counter()
        if self.streaming and not opt.control:
            _warm_stream(self.server, self.live, self.rows_of,
                         self.write_rows, self.pool_h,
                         int(config["warm_upserts"]))
        else:
            _warm_searches(self.server, self.pool_h,
                           int(cell.traffic["search_batch"]))
        self.t_warm = time.perf_counter() - t
        watch.__exit__()
        say(f"set-up: {watch.summary()}")

    def rows_of(self, ids):
        return self.inserts_h[ids - self.n_rows]

    def window(self, sched, trace: bool):
        """Serve ``sched``; returns (requests, window seconds). A trace or
        compile inside fails the run."""
        counters0 = self.server.counters()
        trace_ctx = contextlib.nullcontext()
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            popt = jax.profiler.ProfileOptions()
            popt.python_tracer_level = 0    # host spans are the harness's own
            trace_ctx = jax.profiler.trace(str(TRACE_DIR),
                                           profiler_options=popt)
        with _CompileWatch() as watch, _GcWatch() as gcw, trace_ctx, \
                _span("bench.window", trace):
            if sched.loop == "closed":
                reqs = _window_closed(self.server, sched, self.pool_h,
                                      self.opt.seconds, trace, self.n_rows)
            else:
                reqs = _window_open(self.server, sched, self.pool_h,
                                    self.inserts_h, self.live, self.rows_of,
                                    trace, self.n_rows)
        window_s = max(r.done for r in reqs)
        counters1 = self.server.counters()
        compiled = len(watch.events) + (counters1["compile_count"]
                                        - counters0["compile_count"])
        if compiled or counters1.get("grow_count") != counters0.get(
                "grow_count"):
            raise RunFailure(
                f"{len(watch.events)} traces or compiles inside the window, "
                f"compile_count {counters0['compile_count']} -> "
                f"{counters1['compile_count']}, grow_count "
                f"{counters0.get('grow_count')} -> "
                f"{counters1.get('grow_count')}: set-up missed a program "
                "the window uses")
        late = np.array([r.call - r.due for r in reqs])
        lat = [r.done - r.due for r in reqs if r.kind == SEARCH]
        tail = "/".join(f"{1e3 * np.percentile(lat, q):.3f}"
                        for q in (50, 95, 99))
        say(f"window {window_s:.3f} s, {len(reqs)} requests; search "
            f"p50/p95/p99 {tail} ms; generator lateness median "
            f"{np.median(late) * 1e3:.3f} ms, max {np.max(late) * 1e3:.3f} "
            f"ms; {gcw.summary()}; counters {counters0} -> {counters1}")
        return reqs, window_s


def run_cell(cell: Cell, opt: Options) -> dict:
    """Run ``cell`` once; returns the result object (last stdout line)."""
    device = device_info(cell.chips, opt.rehearse)
    peaks = _peaks(device["kind"])
    if peaks is None and not opt.rehearse:
        raise RunFailure(f"device kind {device['kind']!r} is not in "
                         "peaks.json; add its published peaks")
    say(f"device {device}")
    pool_size = int(_effective_config(cell, opt.rehearse)["shape"]["queries"])
    sched = make_schedule(cell.traffic, opt.seed, opt.seconds, pool_size,
                          opt.rate_per_s)
    st = Served(cell, opt, sched.n_upserts)
    config, shape, server = st.config, st.config["shape"], st.server
    streaming, live, k = st.streaming, st.live, int(shape["k"])
    pool_h, inserts_h, inserts = st.pool_h, st.inserts_h, st.inserts
    corpus, n_rows = st.corpus, st.n_rows
    st.corpus = None
    setup_s = time.perf_counter() - opt.t0
    say(f"setup_s {setup_s:.3f} = start and imports "
        f"{setup_s - st.t_gen - st.t_build - st.t_warm:.3f} + data "
        f"{st.t_gen:.3f} + build {st.t_build:.3f} + warm-up "
        f"{st.t_warm:.3f}")
    reqs, window_s = st.window(sched, opt.trace)
    del st.inserts

    mem = _memory_peak(cell.chips)
    probe = []
    if streaming:     # recall probe on the final live set
        server.settle()
        for x in range(min(int(config["recall_probe"]), pool_size)):
            req = _Req(SEARCH, 0.0, key=np.array([x]), lo=live.lo,
                       hi=live.hi)
            _search(server, req, pool_h[x:x + 1], False)
            probe.append(req)
    work = _adc_work(server, reqs, pool_h)
    server.close()
    del server, st
    gc.collect()

    t = time.perf_counter()
    rows = corpus
    if streaming:
        rows = jnp.concatenate([_data(config)[0], inserts])
    answers = _answers([r for r in reqs if r.kind == SEARCH], pool_h,
                       inserts_h, pool_size)
    readings = check.compare(answers, rows, k)
    if streaming:
        pa = _answers(probe, pool_h, inserts_h, pool_size)
        pr = check.compare(pa, rows, k)
        readings["recall_window"] = readings["recall_at_10"]
        readings["recall_at_10"] = pr["recall_at_10"]
        readings["dist_gap"] = max(readings["dist_gap"], pr["dist_gap"])
        readings["bad_answers"] += pr["bad_answers"]
        readings["bad_examples"] += pr["bad_examples"]
    del rows, corpus
    correct, shown = check.judge(readings, config["check"])
    for ex in readings.pop("bad_examples"):
        say(f"an answer that breaks a guarantee: {ex}")
    say(f"reference {time.perf_counter() - t:.3f} s over {len(answers)} "
        "answered queries"
        + (f" and {len(probe)} probes" if probe else "")
        + f"; readings {readings}")

    ctx = Context(cell=cell, reqs=reqs, window_s=window_s, setup_s=setup_s,
                  readings=readings, work=work, peaks=peaks,
                  memory_peak_bytes=mem, trace=None)
    if opt.trace:
        ctx.trace = tracing.reduce_dir(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device.update(busy_s=ctx.trace.busy_s, window_s=ctx.trace.window_s)
    metrics = read_metrics(cell.per_layer if opt.trace else cell.end_to_end,
                           ctx, strict=not opt.rehearse)
    device["memory_peak_bytes"] = mem
    result = {"correct": bool(correct), "attempted": len(reqs), "failed": 0,
              "metrics": metrics, "device": device}
    if opt.trace:
        result["breakdown"] = ctx.trace.breakdown()
    result["checks"] = shown
    for name, c in shown.items():
        say(f"check {name} = {c['value']!r} (limit {c['limit']})")
    return result


def read_metrics(metrics, ctx, strict: bool) -> dict:
    """{name: {"value", "unit"}} of ``metrics`` read from ``ctx``. Each is
    listed for this cell in BENCHMARK.json, so with ``strict`` a reader
    that finds nothing fails the run: what it reads (a scope, a program, a
    counter) was renamed or taken off the path. A rehearsal on another
    platform has no device trace, and leaves such a metric out."""
    out = {}
    for m in metrics:
        v = m.read(ctx)
        if v is not None:
            out[m.name] = {"value": float(v), "unit": m.unit}
        elif strict:
            raise RunFailure(f"metric {m.name} read nothing in this run; "
                             f"its reader reads: {m.what}")
    return out


def _data(config):
    """(corpus, insertable rows, query pool) of a configuration: the same
    for every run, as a deployment's data set is; runs differ in their
    traffic over it."""
    shape = config["shape"]
    extra = (config["stream"]["row_capacity"] - int(shape["rows"])
             if "stream" in config else 0)
    return make_data(int(config["data_seed"]), int(shape["rows"]), extra,
                     int(shape["queries"]), int(shape["dim"]),
                     int(shape["rows_per_component"]))


def _memory_peak(chips):
    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats()
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _adc_work(server, reqs, pool_h):
    """Bytes and operations the window's searches required, from the
    probed posting mass (read-only ivfpq engines): the ADC scan's, and the
    whole search's (the scan's plus the re-rank's candidate rows)."""
    inputs = server.probe_inputs()
    if inputs is None:
        return None
    reducer, centroids, sizes, knobs = inputs
    keys = np.concatenate([r.key for r in reqs if r.kind == SEARCH])
    uniq, count = np.unique(keys, return_counts=True)
    qr = np.asarray(reducer(jnp.asarray(pool_h[uniq])))
    per_q = probed_rows(qr, centroids, sizes, knobs.nprobe)
    work = adc_work(int(np.sum(per_q * count)), int(keys.size),
                    knobs.pq_subspaces, knobs.pq_centroids, qr.shape[1],
                    knobs.lut_dtype)
    rerank = rerank_work(int(keys.size), knobs.rerank, pool_h.shape[1])
    work["search_bytes"] = work["bytes"] + rerank["bytes"]
    work["search_flops"] = work["flops"] + rerank["flops"]
    return work
