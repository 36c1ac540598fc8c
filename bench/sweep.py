#!/usr/bin/env python3
"""Find an open-loop cell's knee: one set-up, then one window per offered
rate, on the machine it is started on (a TPU, or any platform with
``--rehearse``).

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 100,150,200

The knee is the highest offered rate that the system keeps up with: the
completed rate is at least 99% of the offered one, and the generator's
median lateness over the window's last tenth exceeds that over its first
tenth by less than 10 ms (a queue that grows adds its whole excess of
work to the lateness of every later request; the median, unlike the
mean, does not move with one host stall of a few hundred ms). For each
rate this prints one JSON line: offered and completed rate, median
lateness over the window's first and last tenth, whether it kept up by
that rule, and the search latency median, 95th and 99th percentile.
Correctness is not judged here; the cell's runs judge it.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
# libtpu otherwise keeps its logs at a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

KEPT_UP_SHARE = 0.99          # completed over offered rate
LATENESS_GROWTH_MS = 10.0     # last tenth's median lateness over the first's


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from harness.cell import Options, Served, device_info, say
    from harness.spec import load_cell
    from harness.traffic import SEARCH, make_schedule
    from repro.launch.compile_cache import enable_compile_cache
    cell = load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("a knee is swept for open-loop cells only")
    device = device_info(cell.chips, args.rehearse)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    opt = Options(seed=args.seed, seconds=args.seconds,
                  rehearse=args.rehearse, t0=T0)
    pool = int(cell.config["shape"]["queries"])
    if args.rehearse:
        pool = int(cell.config["rehearse"]["shape"]["queries"])
    rates = [float(r) for r in args.rates.split(",")]
    scheds = [make_schedule(cell.traffic, args.seed + i, args.seconds, pool,
                            rate) for i, rate in enumerate(rates)]
    st = Served(cell, opt, sum(s.n_upserts for s in scheds))
    say(f"device {device}; set up in {time.perf_counter() - T0:.1f} s")
    for rate, sched in zip(rates, scheds):
        reqs, window_s = st.window(sched, False)
        late = np.array([r.call - r.due for r in reqs])
        tenth = max(1, len(reqs) // 10)
        lat = np.array([r.done - r.due for r in reqs if r.kind == SEARCH])
        first, last = (1e3 * float(np.median(x))
                       for x in (late[:tenth], late[-tenth:]))
        completed = len(reqs) / window_s
        print(json.dumps({
            "workload": args.workload, "offered_per_s": rate,
            "completed_per_s": completed,
            "lateness_first_tenth_ms": first,
            "lateness_last_tenth_ms": last,
            "kept_up": bool(completed >= KEPT_UP_SHARE * rate
                            and last - first < LATENESS_GROWTH_MS),
            "search_p50_ms": 1e3 * float(np.median(lat)),
            "search_p95_ms": 1e3 * float(np.percentile(lat, 95)),
            "search_p99_ms": 1e3 * float(np.percentile(lat, 99)),
            "requests": len(reqs), "window_s": window_s,
            "device": device}), flush=True)
        if st.streaming:
            st.server.settle()


if __name__ == "__main__":
    main()
