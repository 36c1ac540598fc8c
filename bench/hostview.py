#!/usr/bin/env python3
"""Where a cell's searches spend the time the chip does not: one traced
window, read for the program's host spans, on the machine it is started on
(a TPU, or any platform with ``--rehearse``).

    python3 bench/hostview.py --workload <cell> --seed <n> --seconds <s>

Sets the cell up as ``run.py`` does and serves one window under the
profiler, then reads the trace with ``harness.host``: the four host
metrics (``prepare_ms.open``, ``launch_ms.open``, ``complete_ms.open``,
``host_idle_ms.open``, through their readers under ``metrics/``), the
device's idle share, and on stderr the 10 longest idle gaps on device 0,
each cut where a search starts and where its wait ends, named by the
innermost host event over it and marked where a search was outstanding. Before the window it times what the program's spans
cost a search (``qpad.search`` with its ``.prepare`` and ``.launch``
children) with no profiler running and with one running. The last
stdout line is one JSON object. Correctness is not judged here; the
cell's runs judge it.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
# libtpu otherwise keeps its logs at a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

READERS = ("prepare_ms.open", "launch_ms.open", "complete_ms.open",
           "host_idle_ms.open")


def span_cost_us(calls: int) -> float:
    """Host microseconds a search spends in its three program spans (each
    a ``jax.profiler.TraceAnnotation``, as ``repro.search.tracing.span``
    makes them)."""
    from jax.profiler import TraceAnnotation as span
    t = time.perf_counter()
    for _ in range(calls):
        with span("qpad.search"):
            with span("qpad.search.prepare"):
                pass
            with span("qpad.search.launch"):
                pass
    return 1e6 * (time.perf_counter() - t) / calls


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--span-calls", type=int, default=200000,
                    help="searches' worth of spans timed for the cost")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from harness import host, trace
    from harness.cell import (TRACE_DIR, Context, Options, Served,
                              device_info, say)
    from harness.spec import BENCH_DIR, _load_reader, load_cell
    from harness.traffic import make_schedule
    from repro.launch.compile_cache import enable_compile_cache
    cell = load_cell(args.workload)
    device = device_info(cell.chips, args.rehearse)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    opt = Options(seed=args.seed, seconds=args.seconds, trace=True,
                  rehearse=args.rehearse, t0=T0)
    pool = int(cell.config["shape"]["queries"])
    if args.rehearse:
        pool = int(cell.config["rehearse"]["shape"]["queries"])
    sched = make_schedule(cell.traffic, args.seed, args.seconds, pool)
    st = Served(cell, opt, sched.n_upserts)

    cost = {"off": span_cost_us(args.span_calls)}
    cost_dir = BENCH_DIR / ".cache" / "span_cost"
    popt = jax.profiler.ProfileOptions()
    popt.python_tracer_level = 0
    with jax.profiler.trace(str(cost_dir), profiler_options=popt):
        cost["on"] = span_cost_us(args.span_calls)
    shutil.rmtree(cost_dir, ignore_errors=True)
    say(f"span cost a search: {cost['off']:.3f} us with no profiler, "
        f"{cost['on']:.3f} us with one running")

    reqs, window_s = st.window(sched, True)
    ctx = Context(cell=cell, reqs=reqs, window_s=window_s,
                  host=host.reduce_dir(TRACE_DIR),
                  trace=trace.reduce_dir(TRACE_DIR))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    metrics = {}
    for name in READERS:
        read, _ = _load_reader(BENCH_DIR / "metrics" / f"{name}.py")
        metrics[name] = read(ctx)
    gaps = ctx.host.idle_gaps()
    for name, thread, secs, busy in gaps:
        say(f"idle gap {1e3 * secs:.3f} ms: {name} [{thread}]"
            + (", a search outstanding" if busy else ""))
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "metrics": metrics,
        "searches_paired": len(ctx.host.pairs),
        "idle_share": 1.0 - ctx.trace.busy_s / ctx.trace.window_s,
        "span_cost_us": cost, "idle_gaps": gaps, "device": device}),
        flush=True)


if __name__ == "__main__":
    main()
