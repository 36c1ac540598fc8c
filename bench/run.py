#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell, its configuration, its traffic mix
and its metrics are found by name through ``BENCHMARK.json``. Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result. ``--rehearse`` runs the cell on any platform at the
configuration's rehearsal sizes (for the tests); its numbers are never
device metrics.

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the correctness
comparison read, beside its limit (also the last lines of stderr).
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


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform, rehearsal sizes; for the tests")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    from harness.cell import Options, run_cell, say
    from harness.spec import load_cell
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    cell = load_cell(args.workload)
    say(f"compile cache {enable_compile_cache()}")
    # every program, however quick to compile, is kept: a run's set-up
    # then compiles nothing that an earlier run in the checkout compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, Options(seed=args.seed, seconds=args.seconds,
                                    trace=bool(args.trace),
                                    rehearse=args.rehearse, t0=T0))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
